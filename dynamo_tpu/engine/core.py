"""Continuous-batching engine core: slots, paged-block allocator, and the
async scheduling loop driving the jitted prefill/decode steps.

The reference's analog is the external engine it orchestrates (vLLM's
scheduler + paged allocator); here it is native. TPU-first specifics:

- one jitted decode program serves the whole batch every step (static
  [max_num_seqs] shapes; inactive slots aim at the trash block and their
  outputs are ignored);
- prefill programs are compiled per bucket length (EngineConfig.prefill_buckets)
  so XLA sees only static shapes;
- KV caches are donated through every step call → XLA updates HBM in place;
- cancellation is step-granular: each loop iteration polls request contexts
  (an in-flight XLA dispatch is never interrupted), matching the semantics
  the runtime's EngineContext promises (SURVEY.md §7 "hard parts").
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..llm.kv.blocks import TokenBlockSequence
from ..llm.kv.offload import OffloadJob
from ..llm.kv.pool import KvBlockManager
from .block_copy import scatter_blocks_from_host
from ..llm.kv_router.protocols import ForwardPassMetrics
from ..llm.protocols.common import FinishReason
from .attention import wave_contig_table
from .config import EngineConfig, ModelConfig
from .index_scores import key_wave_blocks
from .models import llama, module_for
from .sampling import SlotSampling, make_slot_keys, sample_tokens

logger = logging.getLogger("dynamo_tpu.engine")


def _owned(a: np.ndarray) -> jax.Array:
    """A device array from a numpy copy that nothing else holds: a
    transfer may read its host buffer after the call returns (jnp.array of
    an ndarray does not copy it first), and the engine's host mirrors are
    mutated by the next iteration while a dispatch built from them is
    still queued behind the one in flight."""
    return jnp.asarray(a.copy())


def _own_suffix_start(pool, blocks: list, step: int = 128) -> int:
    """Where the trailing run of blocks that ``blocks``' sequence alone
    holds (refcount 1) begins: what the defrag pass may move. Asked of the
    pool from the end, ``step`` blocks at a time: the scan runs every cycle
    over every slot, and a context of 2,000 shared blocks behind 50 of a
    sequence's own (a long cached document) must not cost 2,000 lookups a
    slot (measured, PR 42: 42 ms a cycle at 64 slots of 33k tokens)."""
    j = len(blocks)
    while j > 0:
        lo = max(0, j - step)
        rcs = pool.refcounts(blocks[lo:j])
        k = len(rcs)
        while k > 0 and rcs[k - 1] == 1:
            k -= 1
        if k > 0:
            return lo + k
        j = lo
    return 0


@dataclasses.dataclass
class EngineRequest:
    """One sequence's engine-side state."""

    rid: str
    prompt: List[int]
    sampling: SlotSampling
    max_new_tokens: int
    eos_ids: frozenset
    ctx: object = None            # runtime EngineContext (cancellation)
    out_queue: asyncio.Queue = dataclasses.field(default_factory=asyncio.Queue)
    # disaggregation (SURVEY.md §7 stage 7):
    # - prefill worker: async callback(first_token, logprob, host_values,
    #   seq_hashes) shipping the prompt's KV blocks to the decode engine;
    #   the request finishes after prefill (the reference's max_tokens=1
    #   remote-decode prefill, examples/llm/components/prefill_worker.py).
    handoff: object = None
    # - device mode: handoff receives the DEVICE gather ({"stacked", ...})
    #   instead of host wire values — the in-process ICI bulk plane
    #   (llm/kv_transport.py); no device→host fetch happens at all.
    handoff_device: bool = False
    # - wire mode with layer streaming negotiated (llm/kv/stream.py): the
    #   handoff receives a LayeredHarvest (per-layer device→host fetches)
    #   instead of whole-stack host values, so the prefill worker chains
    #   per-layer DATA frames while later layers are still fetching
    handoff_layered: bool = False
    # - decode worker: KV arrived from a remote prefill (KvPayload with
    #   host wire values, or kv_transport.DeviceKvPayload with device
    #   arrays); admission scatters it instead of running the prefill
    #   program.
    precomputed: object = None
    # engine state
    slot: int = -1
    blocks: List[int] = dataclasses.field(default_factory=list)
    pos: int = 0                  # tokens currently in KV
    generated: int = 0
    # monotone per-request PRNG step: equals `generated` until a
    # preemption, after which it keeps advancing so recompute never reuses
    # consumed sampling keys (seeded streams stay reproducible under load)
    key_step: int = 0
    last_token: int = -1
    # False while the admission prefill's sampled token is still being
    # fetched from the device (it completes after the next decode
    # dispatch, _admit_with_plan's `defer`): the slot is held but excluded
    # from decode until completion
    ready: bool = True
    prefix_hit_tokens: int = 0
    seq: Optional[TokenBlockSequence] = None   # full token history + hashes
    registered_blocks: int = 0
    # the window-pool blocks it holds (llm/kv/pool.py WindowBlocks; None on
    # a model whose window rows are not pool blocks)
    win: object = None
    # a resident drafter's guess at the token after last_token (a device
    # scalar while the admission's fetch is deferred; -1: none yet)
    draft: object = -1
    emitted_total: int = 0        # tokens the client has seen (across lives)
    # lane-prefill mode (EngineConfig.lane_prefill_max_tokens): the FULL
    # prompt (incl. any prefix-hit tokens); while pos < len(lane_prompt)
    # the slot's decode inputs come from here ("planned" tokens) and
    # sampled outputs are discarded — the step consuming the last prompt
    # token yields the first real generation. None = normal admission.
    lane_prompt: Optional[List[int]] = None
    # client-stream indices where the next token was derived through a
    # DIFFERENT compiled program than an uncontended prefill-path run would
    # use: recompute preemptions (prefill re-derives the boundary token)
    # and lane admissions (the decode program derives the first token).
    # Bit-exactness vs an uncontended run is guaranteed only UP TO the
    # first of these — f32 numerics differ across program shapes and can
    # legitimately flip a greedy argmax at near-tie logits (KNOWN_ISSUES).
    numeric_boundaries: List[int] = dataclasses.field(default_factory=list)
    # speculative decoding (engine/spec/): max drafts verified per
    # dispatch for THIS request. -1 = follow the engine's live default
    # (EngineCore.spec_k_live, llmctl spec set-k); 0 = explicitly off;
    # n > 0 clamps to the compiled maximum EngineConfig.spec_k.
    spec_k: int = -1
    # multi-tenant serving plane (llm/tenancy.py): tenant attributes
    # this request's registered KV blocks in the tiers' quota ledger
    # ("" = the implicit single tenant — untenanted behavior exactly);
    # session groups requests for exported-trace prefix structure.
    tenant: str = ""
    session: str = ""
    enqueue_time: float = dataclasses.field(default_factory=time.monotonic)
    first_token_time: Optional[float] = None
    # when the admission's prefill dispatch (or precomputed scatter, or
    # the lane admission itself) returned: the start of the trace's
    # engine.first_token span
    dispatched_time: Optional[float] = None
    # when a slot and a KV plan existed for it (the end of its queue
    # wait), and how many prefill dispatches that admission issued: the
    # `first_token` flight record's stamps, overwritten by a recompute
    admitted_time: Optional[float] = None
    prefill_chunks: int = 0
    # the request's runtime Trace (runtime/tracing.py) — attached by
    # submit() from the ambient contextvar so the engine can feed
    # per-phase spans (queue wait, KV onboard incl. fabric fetch,
    # preemption markers) into the same fleet trace the frontend opened.
    # Kept as `object` to stay dependency-light; None = untraced.
    trace: object = None

    # tier-hit onboard prep failed once: the re-admission skips the
    # host/disk/remote cascade and recomputes cold (graceful fallback —
    # a broken tier must never make serving worse than no tier)
    cold_admission: bool = False

    @property
    def cancelled(self) -> bool:
        """Client-stop OR deadline-exceeded — both vacate the slot the
        same way; _finish_request counts them apart."""
        if self.ctx is None:
            return False
        return bool(self.ctx.is_stopped
                    or getattr(self.ctx, "deadline_exceeded", False))


_FINISH = object()  # queue sentinel

# The most event-loop iterations one engine cycle's yield runs. A request on
# a new connection needs six or seven of them from the poll that finds it to
# its submit(): accept, the accepted transport's task, connection_made and
# add_reader, the first read, the handler task's wake-up, a body that came
# in a second segment, the pipeline's hand-over. Twice that, so a chain that
# grows a hop still fits one cycle. The bound is what lets a task that spins
# on ``await asyncio.sleep(0)`` beside the engine (the KV tiers' pumps, the
# router indexer's drain) share the loop: beside one the ready queue never
# empties, and an unbounded drain would never dispatch again.
YIELD_DRAIN_MAX_ITERS = 16


def _loop_is_quiet(loop: asyncio.AbstractEventLoop) -> bool:
    """Nothing else is ready to run on ``loop`` right now, asked by a task
    that has just resumed from ``asyncio.sleep(0)``. CPython's
    ``BaseEventLoop`` keeps its ready handles in ``_ready``: the caller's
    own has been popped, what this iteration's poll found and what its
    callbacks scheduled lie behind it. A loop that keeps no such queue
    (uvloop, a test's loop) reads as quiet: one iteration a yield."""
    try:
        return len(loop._ready) == 0        # type: ignore[attr-defined]
    except (AttributeError, TypeError):
        return True


class EngineCore:
    """The model-executing scheduler. Owns params + KV cache on device."""

    # set by __init__ from the model; the default is for a shell that
    # builds the step functions only (benchmark/compile_check.py's, which
    # sets what _compile_jits read before the hybrid family came)
    is_hybrid = False
    # the model's own multi-token-prediction module drafts (--spec-k on a
    # model with ``mtp_layers``): the prefill program returns a draft and
    # the decode step scores two rows a slot (docs/speculative.md)
    resident_drafter = False

    def __init__(self, model_cfg: ModelConfig, engine_cfg: EngineConfig,
                 params: Optional[dict] = None, attn_impl: str = "auto",
                 param_dtype=jnp.bfloat16, mesh=None,
                 kv_event_publisher=None):
        if engine_cfg.kv_block_size == 0:
            # bring-up auto-selection (EngineConfig.auto_kv_block_size —
            # the round-5 small-C finding, promoted from a bench.py-only
            # default): resolved HERE, before anything reads the block
            # size, so every downstream consumer sees a concrete value
            engine_cfg = dataclasses.replace(
                engine_cfg,
                kv_block_size=EngineConfig.auto_kv_block_size(
                    model_cfg, engine_cfg.kv_quantization))
        self.model_cfg = model_cfg
        self.cfg = engine_cfg
        self.mesh = mesh
        # pipeline parallelism (parallel/pipeline_parallel.py): the mesh
        # is authoritative — a "pp" axis switches param/KV placement and
        # the whole compiled program set to the token-interleaved stage
        # ring. EngineConfig.pp must agree when set (every rank of a
        # multihost engine builds from identical flags).
        self.pp = (mesh.shape["pp"]
                   if mesh is not None and "pp" in mesh.axis_names else 1)
        if engine_cfg.pp > 1 and engine_cfg.pp != self.pp:
            raise ValueError(
                f"EngineConfig.pp={engine_cfg.pp} but the mesh carries "
                f"pp={self.pp} — build the mesh with make_pp_mesh(pp, tp)")
        if self.pp > 1:
            # the mesh can carry pp the config never saw (tests build
            # meshes directly): re-run the config-level pp validation
            # against the REAL stage count, then the model-level checks
            dataclasses.replace(engine_cfg, pp=self.pp)  # raises on misuse
        # the module that serves this configuration says what it cannot
        # run under: every unsupported combination refuses loudly HERE, at
        # build, not by serving garbage (docs/dsa.md, docs/hybrid_cache.md)
        self.model_mod = module_for(model_cfg)
        if model_cfg.mtp_layers and engine_cfg.spec_k <= 0:
            # the module is served as the model's drafter or not held
            model_cfg = dataclasses.replace(model_cfg, mtp_layers=0)
            self.model_cfg = model_cfg
            if params is not None:
                params = {k: w for k, w in params.items()
                          if not k.startswith("mtp.")}
        self.resident_drafter = model_cfg.mtp_layers > 0
        refused = self.model_mod.refusals(model_cfg, engine_cfg, mesh)
        if refused:
            raise NotImplementedError(
                f"{model_cfg.model_type} is not implemented with: "
                + "; ".join(refused))
        # the pool row's format (one opaque latent row or heads), and
        # per-slot state behind the prefill table: what the cache's layout
        # says (llm/kv/hybrid.py has_state), whatever the family
        self.is_mla = model_cfg.kv_lora_rank > 0
        layout = self.model_mod.cache_layout(
            model_cfg, engine_cfg.kv_block_size,
            jnp.dtype(param_dtype).itemsize)
        self.is_hybrid = layout is not None and layout.has_state
        if (model_cfg.sliding_window is not None and not self.is_hybrid
                and engine_cfg.max_model_len <= model_cfg.sliding_window):
            # the window can never bind at this serving length: drop it so
            # decode keeps the Pallas-eligible path (window masking forces
            # the XLA gather implementation)
            model_cfg = dataclasses.replace(model_cfg, sliding_window=None)
            self.model_cfg = model_cfg
        _rs = model_cfg.rope_scaling
        if (_rs is not None and _rs.rope_type == "longrope"
                and _rs.longrope_active == "auto"
                and engine_cfg.max_model_len
                <= _rs.original_max_position_embeddings):
            # every servable sequence fits the pretrained window, so the
            # SHORT factors are HF-exact for all of them (HF switches to
            # long only past original_max); the attention scaling stays
            # config-derived either way (llama.rope_attention_scaling)
            model_cfg = dataclasses.replace(
                model_cfg, rope_scaling=dataclasses.replace(
                    _rs, longrope_active="short"))
            self.model_cfg = model_cfg
        self.statics = llama.ModelStatics(
            cfg=model_cfg, block_size=engine_cfg.kv_block_size,
            attn_impl=attn_impl,
            kv_coalesce=engine_cfg.kv_contig_alloc,
            table_blocks=engine_cfg.max_blocks_per_seq,
            # heads shard over "tp": the attention kernels then run per
            # shard (llama._per_tp_shard). The pp stage ring is already
            # inside its own shard_map and hands kernels local arrays.
            mesh=(mesh if mesh is not None and self.pp == 1
                  and mesh.shape.get("tp", 1) > 1 else None),
            # any mesh may shard the expert stacks: the experts then
            # stay dense over E (llama.experts_run_grouped)
            sharded=mesh is not None)
        if engine_cfg.quantization not in ("none", "int8", "int8-noembed",
                                           "int4", "int4-noembed"):
            raise ValueError(
                f"unknown quantization {engine_cfg.quantization!r}")
        quantized = engine_cfg.quantization != "none"
        # int4 = grouped-int4 dense matmuls + lm_head, int8 embed
        # (quant.py module docstring); -noembed leaves the embed in the
        # load dtype for either width
        qbits = 4 if engine_cfg.quantization.startswith("int4") else 8
        qembed = not engine_cfg.quantization.endswith("-noembed")
        if params is None and quantized:
            # streaming init→quantize: never materializes the full bf16
            # tree (16 GB for 8B geometry — OOM on one 16 GB v5e)
            from .quant import init_params_quantized
            params = init_params_quantized(
                model_cfg, jax.random.PRNGKey(engine_cfg.seed),
                dtype=param_dtype, include_embed=qembed, bits=qbits)
        elif params is None:
            params = self.model_mod.init_params(
                model_cfg, jax.random.PRNGKey(engine_cfg.seed), dtype=param_dtype)
        elif quantized:
            from .quant import quantize_params
            params = quantize_params(
                params, include_embed=qembed, bits=qbits)
        if mesh is None:
            # single-device decode perf: wq|wk|wv → wqkv, gate|up →
            # gateup (llama.fuse_stacked_matmuls). The gate is ANY mesh,
            # not just tp: under tp the fused out axis cannot carry the
            # column permutation the TP-8 projection was flagged for,
            # and under pp (even tp=1) the stage ring shards the UNFUSED
            # per-tensor layout — a pp mesh silently taking the fused
            # path would break pp_param_pspecs' per-key placement
            # (test_pipeline_parallel asserts no fused keys on a pp
            # core). dict(): the transform deletes split keys — never
            # from the caller's own tree
            params = llama.fuse_stacked_matmuls(dict(params), model_cfg)
        self.params = params
        kv_shards = 1
        if (mesh is not None and engine_cfg.kv_quantization != "none"
                and not self.is_mla):
            # llama pools only: the MLA latent pool replicates under tp
            # (no per-shard scale sections; mla.init_kv_cache)
            # int8 + tensor parallelism: the pool row carries one
            # (values, scales) section per tp shard so the lane-axis tp
            # sharding never splits a scale group (attention.py
            # quantize_kv_rows groups)
            kv_shards = mesh.shape.get("tp", 1)
            if model_cfg.num_kv_heads % kv_shards != 0:
                raise ValueError(
                    f"kv_quantization with tp={kv_shards} needs tp to "
                    f"divide the KV head count "
                    f"({model_cfg.num_kv_heads}) — each tp shard must "
                    f"own whole heads to carry its own in-row scale "
                    f"group")
        # the arrays the engine holds, the layout the block manager pages
        # them by (None: paged rows only) and the window pool's blocks; a
        # replay builds its fresh pool by the same call (replay.py)
        self.fresh_kv = lambda: self.model_mod.engine_cache(
            model_cfg, engine_cfg, param_dtype, kv_shards)
        self.kv, layout, win_blocks = self.fresh_kv()
        if mesh is not None and self.pp > 1:
            # pp(×tp) placement: layer stacks + KV pool shard L over the
            # stage ring; embed/final_norm/lm_head replicate (the last
            # stage samples locally). Validates layer divisibility and
            # the sliding-window refusal up front.
            from ..parallel.pipeline_parallel import (place_pp,
                                                      pp_split_config)
            pp_split_config(self.statics, self.pp)
            self.params, self.kv = place_pp(self.params, self.kv, mesh,
                                            model_cfg)
            if model_cfg.lm_head_pallas:
                # the stage's in-shard_map _logits has no Pallas
                # partitioning rule — route to the XLA head paths
                model_cfg = dataclasses.replace(model_cfg,
                                                lm_head_pallas=False)
                self.model_cfg = model_cfg
                self.statics = dataclasses.replace(self.statics,
                                                   cfg=model_cfg)
        elif mesh is not None:
            # place params/KV under the tp/sp layout; every jitted step then
            # runs SPMD over the mesh with XLA-inserted ICI collectives
            from ..parallel.sharding import shard_kv, shard_params
            self.params = shard_params(self.params, mesh, model_cfg)
            self.kv = shard_kv(self.kv, mesh)
            if mesh.shape.get("tp", 1) > 1 and model_cfg.lm_head_pallas:
                # the head is vocab-sharded over tp; the fused Pallas head
                # cannot partition — route _logits to the XLA paths
                model_cfg = dataclasses.replace(model_cfg,
                                                lm_head_pallas=False)
                self.model_cfg = model_cfg
                self.statics = dataclasses.replace(self.statics,
                                                   cfg=model_cfg)
        self.kv_event_publisher = kv_event_publisher
        host_pool = None
        self.offload_engine = None
        self.disk_store = None
        self.spill_engine = None
        self._pending_spills: List[int] = []
        if engine_cfg.host_kv_blocks > 0:
            from ..llm.kv.offload import KvOffloadEngine, make_host_pool
            host_pool = make_host_pool(
                engine_cfg.host_kv_blocks, model_cfg,
                engine_cfg.kv_block_size, engine_cfg.kv_quantization,
                int(next(iter(self.kv.values())).shape[-1]), param_dtype)
        if engine_cfg.kv_disk_blocks > 0:
            # G3 tier (llm/kv/diskstore.py): content-addressed on-disk
            # block store under the host pool — host evictions spill
            # there (write-behind), disk hits promote through the
            # off-thread onboard path, and acknowledged blocks survive
            # kill -9 (warm restart). __post_init__ guaranteed the host
            # tier exists.
            from ..llm.kv.diskstore import DiskKvStore, DiskSpillEngine
            self.disk_store = DiskKvStore(
                engine_cfg.kv_disk_dir, engine_cfg.kv_disk_blocks,
                expect_block_size=engine_cfg.kv_block_size)
            self.spill_engine = DiskSpillEngine(
                self.disk_store, on_commit=self._emit_kv_disk_store)
            host_pool.on_evict = self._on_host_evict
        self.remote_store = None
        self.remote_spill_engine = None
        self.kv_fabric = None            # llm/kv/fabric.py, attached at run
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        if engine_cfg.kv_remote_dir:
            # G4 tier (llm/kv/remotestore.py): the fleet fabric's durable
            # rung — disk-tier capacity evictions promote to the shared
            # object store (write-behind, acknowledged iff durable), and
            # remote hits onboard through the same off-thread path as
            # disk. The peer-worker backend attaches at runtime
            # (attach_kv_fabric). __post_init__ guaranteed the disk tier
            # exists.
            from ..llm.kv.diskstore import DiskSpillEngine
            from ..llm.kv.remotestore import ObjectKvBackend, RemoteKvStore
            self.remote_store = RemoteKvStore(ObjectKvBackend(
                engine_cfg.kv_remote_dir, engine_cfg.kv_remote_blocks))
            self.remote_spill_engine = DiskSpillEngine(
                self.remote_store, on_commit=self._emit_kv_remote_store)
            self.disk_store.on_evict = self._on_disk_evict
        self.kv_manager = KvBlockManager(
            engine_cfg.num_kv_blocks, engine_cfg.kv_block_size,
            enable_reuse=engine_cfg.enable_prefix_reuse,
            on_stored=self._on_block_stored,
            on_removed=self._on_block_removed, host_pool=host_pool,
            disk_store=self.disk_store, remote_store=self.remote_store,
            layout=layout, win_blocks=win_blocks)
        if host_pool is not None:
            self.offload_engine = KvOffloadEngine(
                host_pool, engine_cfg.kv_block_size,
                get_kv=lambda: self.kv,
                release_holds=self.kv_manager.pool.release,
                simulated_gbps=engine_cfg.offload_simulated_gbps or None,
                on_store=self._emit_kv_store)
        self.M = engine_cfg.max_blocks_per_seq
        self.B = engine_cfg.max_num_seqs
        # window rows as blocks of a second pool (dots3_note): a decode
        # table carries their ring of R entries behind its M
        self.has_window_pool = self.kv_manager.win_pool is not None
        self.R = layout.ring_blocks if self.has_window_pool else 0
        # flight-record arithmetic of a cache with a layout: the window a
        # window layer reads of a context, and the recurrent bytes one
        # slot-step reads and writes (None / 0 without one)
        self._window = layout.window if layout is not None else None
        self._step_state_bytes = (
            2 * layout.state_layers * layout.state_bytes
            if layout is not None else 0)
        # the groups this cache holds beside the paged rows, which neither
        # disagg plane nor the KV fabric ships (submit, attach_kv_fabric;
        # docs/dsa.md, docs/hybrid_cache.md); empty: paged rows only
        self.beside_paged_rows = tuple(name for name, held in (
            ("an index-key array", "idx" in self.kv),
            ("per-slot state and window rings",
             layout is not None and not layout.window_pool),
            ("a window pool", self.has_window_pool)) if held)
        # blocks per wave of the index-key read (engine/index_scores.py),
        # for the decode records' key_waves / key_run_waves; 0 = no indexer
        self._key_wave_blocks = (
            key_wave_blocks(self.M, engine_cfg.kv_block_size,
                            model_cfg.index_head_dim,
                            self.kv["idx"].dtype.itemsize)
            if "idx" in self.kv else 0)
        # jitted cross-quant repack converters, keyed by the payload's
        # (lane width, dtype); shapes re-specialize inside each jit cache
        self._repack_jits: dict = {}

        self.slots: List[Optional[EngineRequest]] = [None] * self.B
        # optional engine.replay.Recorder capturing the schedule decision
        # log (dispatch inputs in device order) for deterministic replay
        self.recorder = None
        self._pending: Optional[dict] = None   # un-harvested decode dispatch
        self._ragged_pending: Optional[dict] = None  # pipelined ragged
        self._admissions: List[tuple] = []     # (req, tok_dev, logprob_dev)
        self._onboards: List[tuple] = []  # (req, slot, plan, prepped,
        #                                    remote_values-for-recorder)
        self._onboard_tasks: set = set()
        self._handoff_tasks: set = set()
        self.waiting: asyncio.Queue[EngineRequest] = asyncio.Queue()
        # every submitted-not-finished request by id (slots/waiting
        # alone can miss one mid-admission) — _fail_pending's registry
        self._inflight_reqs: dict = {}
        self._dead: Optional[BaseException] = None
        self._work_event = asyncio.Event()
        self._loop_task: Optional[asyncio.Task] = None
        self._stopping = False
        self._step = 0
        # host mirrors of per-slot state
        self._block_tables = np.zeros((self.B, self.M + self.R),
                                      dtype=np.int32)
        self._positions = np.zeros((self.B,), dtype=np.int32)
        self._tokens = np.zeros((self.B,), dtype=np.int32)
        self._samp = {
            "temperature": np.zeros((self.B,), np.float32),
            "top_k": np.zeros((self.B,), np.int32),
            "top_p": np.ones((self.B,), np.float32),
        }
        self._seeds = np.zeros((self.B,), np.int64)
        # "no slot": the default of the per-slot flags a dispatch takes
        self._no_slot = np.zeros((self.B,), dtype=bool)
        # speculative decoding (engine/spec/): host-side drafter + the
        # live draft budget (llmctl spec set-k moves it within
        # [0, cfg.spec_k]; the verify program's shape is compiled at
        # cfg.spec_k+1 rows and never widens at runtime)
        self.spec_k_live = engine_cfg.spec_k
        self.drafter = None
        if engine_cfg.spec_k > 0 and not self.resident_drafter:
            from .spec import PromptLookupDrafter
            self.drafter = PromptLookupDrafter(
                max_ngram=engine_cfg.spec_ngram_max,
                min_ngram=engine_cfg.spec_ngram_min,
                window=engine_cfg.spec_window)
        # the engine seed's key, an argument of the decode program
        self._base_key = jax.random.PRNGKey(engine_cfg.seed)
        self._compile_jits()
        # serving stats
        self.total_prefill_tokens = 0
        self.total_decode_tokens = 0
        self.preemptions = 0
        # deferred-harvest decode: times the in-flight dispatch was
        # harvested with no successor queued behind it, by cause (_drain)
        self.pipeline_drains: Dict[str, int] = {}
        self.lane_admissions = 0
        self.host_onboards = 0
        # contiguity-aware layout (docs/kv_layout.md): defrag passes run
        # + blocks migrated; per-move truth lives on the pool
        # (defrag_moves_total — relocate() increments it)
        self.defrag_passes = 0
        self._defrag_last_step = -(1 << 30)
        # disk (G3) tier: promote-path admissions + blocks restored
        self.disk_onboards = 0
        self.disk_onboarded_blocks = 0
        # remote (G4) fabric tier: fetch-path admissions + the graceful
        # fallbacks (a failed peer fetch recomputes, never errors)
        self.remote_onboards = 0
        self.remote_onboarded_blocks = 0
        self.remote_fetch_failures = 0
        # prefill-as-a-service (components/prefill_service.py): prefix
        # blocks this engine published to the durable object tier
        self.prefill_published_blocks = 0
        # streaming layer-wise KV handoff (llm/kv/stream.py): layers this
        # DECODE engine progressively scattered, stream admissions that
        # fell back (torn → monolithic fill, dead stream → cold
        # recompute), and the transfer-overlap split — busy seconds the
        # engine spent prepping/scattering already-arrived layers (work
        # hidden behind the in-flight transfer) vs seconds it sat exposed
        # waiting on the wire. The nv_llm_disagg_stream_* gauge feed.
        self.disagg_stream_admits = 0
        self.disagg_stream_layers_scattered = 0
        self.disagg_stream_fallbacks = 0
        self.disagg_stream_hidden_s = 0.0
        self.disagg_stream_exposed_s = 0.0
        self._stream_tasks: set = set()
        # end-to-end cancellation/deadlines (docs/chaos.md): requests
        # vacated because the client stopped caring (disconnect → KILL
        # frame → ctx.kill) vs because their wire-propagated deadline
        # budget ran out engine-side — the nv_llm_requests_cancelled_
        # total / _deadline_exceeded_total feeds
        self.requests_cancelled_total = 0
        self.requests_deadline_exceeded_total = 0
        # multi-tenant serving plane (llm/tenancy.py): attached by
        # enable_tenancy() — per-tenant block ledger threaded through the
        # device/host/disk/remote tiers (quota-preferred eviction) plus
        # per-tenant admission counters, the nv_llm_tenant_* gauge feed
        self.tenancy = None
        self.tenant_admitted: dict = {}
        self.tenant_hits: dict = {}
        self.tenant_queries: dict = {}
        # tier-hit onboards whose off-thread prep failed and were
        # re-admitted COLD (full recompute) instead of erroring out
        self.onboard_cold_retries = 0
        # measured prefill rate feed for the fabric's admission gate and
        # the router's NetKV scoring: wall seconds spent in prefill
        # admissions (dispatch + host glue — an upper bound, so the
        # modeled recompute it feeds is conservative). The RATE the gate
        # prices with is age-weighted (fabric.PrefillRateEstimator) so
        # XLA-compile-inflated early admissions on a young engine don't
        # skew fetch-vs-recompute pricing.
        from ..llm.kv.fabric import PrefillRateEstimator
        self.prefill_rate_estimator = PrefillRateEstimator()
        # ragged-dispatch stats (nv_llm_ragged_* metrics feed;
        # docs/ragged_attention.md). "saved" counts the split-path
        # dispatches each ragged batch stood in for, minus itself
        # (ragged.RaggedBatch.dispatches_replaced).
        self.ragged_dispatches = 0
        self.ragged_rows_total = 0
        self.ragged_prefill_rows_total = 0
        self.ragged_decode_rows_total = 0
        self.ragged_mixed_dispatches = 0
        self.ragged_dispatches_saved = 0
        # ragged×spec: draft rows that rode ragged dispatches (the
        # nv_llm_ragged_spec_rows_total feed); acceptance rides the
        # shared spec_* counters below
        self.ragged_spec_rows = 0
        # cross-sequence wave prefetch (attention.ragged_prefetch_counts
        # — the host-side mirror of the kernel's parity chain): first
        # waves seen / first waves a predecessor prefetched
        self.ragged_first_waves = 0
        self.ragged_prefetched_waves = 0
        # speculation stats (nv_llm_spec_* metrics feed)
        self.spec_dispatches = 0       # verify dispatches issued
        self.spec_drafted_tokens = 0   # draft tokens scored
        self.spec_accepted_tokens = 0  # drafts that matched their sample
        self.spec_emitted_tokens = 0   # tokens emitted by verify steps
        self.spec_rewound_rows = 0     # rows scored and rolled back
        # flight recorder (engine/flight_recorder.py): bounded ring of
        # per-dispatch records + loop-lag probe, dumpable via /debug and
        # llmctl trace dump; per-phase spans feed each request's trace.
        # Its PhaseClock is the loop's one clock: every line of the loop
        # runs in exactly one phase, and each decode/ragged/verify record
        # carries the split of the cycle it closes
        from .flight_recorder import FlightRecorder, register_recorder
        self.flight = FlightRecorder()
        self.clock = self.flight.clock
        register_recorder(self.flight)

    # ------------------------------------------------------------------ jit
    def _compile_jits_pp(self) -> None:
        """Pipeline-parallel program set (parallel/pipeline_parallel.py),
        with the SAME host-facing contracts as the single-device
        programs — prefill(params, kv, tokens, table, start_pos,
        true_len, key, temp, top_k, top_p) → (tok, logprob, kv) and the
        K-step decode scan's (toks [K,B], logprobs [K,B], kv). Keeping
        the contracts identical is what makes every engine path —
        dispatch pipelining, harvest, preemption, lane prefill, chunked
        prefill, engine/replay.py and the multihost followers' stage
        dispatches — compose with pp UNCHANGED: followers and the
        offline replayer re-issue the recorded events through these same
        jits. The single-step _decode_jit has no pp form (EngineConfig
        requires K > 1); spec verify and sp prefill are refused at
        bring-up."""
        from ..parallel.pipeline_parallel import (pp_decode_k_forward,
                                                  pp_prefill_forward)
        statics = self.statics
        mesh = self.mesh
        K = self.cfg.decode_steps_per_dispatch

        def prefill(params, kv, tokens, block_table, start_pos, true_len,
                    key, temperature, top_k, top_p):
            logits, kv = pp_prefill_forward(
                params, kv, tokens, block_table, start_pos, true_len,
                statics, mesh)
            tok, logprob = sample_tokens(
                logits[None, :], key[None], temperature[None],
                top_k[None], top_p[None])
            return tok[0], logprob[0], kv

        self._prefill_jit = jax.jit(prefill, donate_argnums=(1,))
        self._decode_jit = None

        def decode_k(params, kv, tokens, positions, block_tables,
                     seeds, steps0, temperature, top_k, top_p,
                     planned, planned_mask, base_key):
            return pp_decode_k_forward(
                params, kv, tokens, positions, block_tables, seeds,
                steps0, temperature, top_k, top_p, planned,
                planned_mask, statics, mesh, K, base_key)

        self._decode_k_jit = jax.jit(decode_k, donate_argnums=(1,))
        self._planned_zero = (jnp.zeros((K, self.cfg.max_num_seqs),
                                        jnp.int32),
                              jnp.zeros((K, self.cfg.max_num_seqs), bool))
        self._merge_jit = jax.jit(
            lambda dev_k, host, mask: jnp.where(mask, dev_k[-1], host))
        self._verify_jit = None
        self._ragged_jit = None   # EngineConfig refuses ragged + pp
        self._ragged_row_sampled = False
        self._prefill_sp_jit = None
        self._sp = 1

    def _compile_jits(self) -> None:
        if self.pp > 1:
            self._compile_jits_pp()
            return
        statics = self.statics
        # packed-int4 weights unpack ONCE at the top of every program —
        # a K-step decode dispatch then reads S4 at packed bandwidth
        # (engine/quant.py module docstring: stored leaves stay int8)
        from .quant import unpack_params

        def prefill(params, kv, tokens, block_table, start_pos, true_len,
                    key, temperature, top_k, top_p):
            params = unpack_params(params)
            extra = {}
            if self.is_hybrid:
                # the slot rides behind the table's M entries
                # (_prefill_table); a hand-driven [M] table is slot 0
                extra = {"slot": (block_table[self.M]
                                  if block_table.shape[0] > self.M else 0)}
                block_table = block_table[:self.M]
            logits, kv = self.model_mod.prefill_forward(
                params, kv, tokens, block_table, start_pos, true_len,
                statics, **extra)
            tok, logprob = sample_tokens(
                logits[None, :], key[None], temperature[None], top_k[None],
                top_p[None])
            return tok[0], logprob[0], kv

        if self.resident_drafter:
            def prefill(params, kv, tokens, block_table, start_pos,  # noqa: F811
                        true_len, key, temperature, top_k, top_p, next_tok):
                """The prefill program with the module's tail: next_tok is
                the prompt's token after the chunk (< 0 after the last
                chunk: the token sampled here) → (..., kv, the first
                draft)."""
                def sample(logits):
                    tok, logprob = sample_tokens(
                        logits[None, :], key[None], temperature[None],
                        top_k[None], top_p[None])
                    return tok[0], logprob[0]
                tok, logprob, draft_logits, kv = \
                    self.model_mod.prefill_forward_mtp(
                        unpack_params(params), kv, tokens, block_table,
                        start_pos, true_len, next_tok, statics, sample)
                return tok, logprob, kv, jnp.argmax(
                    draft_logits, axis=-1).astype(jnp.int32)

        # named scopes: stable names in the compiled programs and the
        # profiler's trace, whatever the compiler calls its fusions
        self._prefill_jit = jax.jit(jax.named_scope("prefill")(prefill),
                                    donate_argnums=(1,))

        def decode(params, kv, tokens, positions, block_tables,
                   keys, temperature, top_k, top_p):
            params = unpack_params(params)
            logits, kv = self.model_mod.decode_forward(
                params, kv, tokens, positions, block_tables, statics)
            toks, logprobs = sample_tokens(logits, keys, temperature,
                                           top_k, top_p)
            return toks, logprobs, kv

        self._decode_jit = jax.jit(jax.named_scope("decode")(decode),
                                   donate_argnums=(1,))

        # The served decode program: K steps fused into one dispatch
        # (EngineConfig decode_steps_per_dispatch), K = 1 included. The
        # per-slot keys are derived inside it from (seeds, steps) and the
        # engine seed's key — an argument (_base_key), so that one
        # compiled program serves every seed — the sampled token feeds
        # the next step ON DEVICE, and the host
        # harvests [K, B] tokens once per dispatch. _decode_jit above is
        # the same step with host-made keys, for callers that drive one
        # step by hand (bench.py, benchmark/selftest.py); the loop never
        # calls it, so it costs no compile.
        K = self.cfg.decode_steps_per_dispatch
        seed = self.cfg.seed

        def decode_k(params, kv, tokens, positions, block_tables,
                     seeds, steps0, temperature, top_k, top_p,
                     planned, planned_mask, base_key):
            params = unpack_params(params)
            # planned [K, B] / planned_mask [K, B]: lane-prefill slots feed
            # predetermined prompt tokens per step instead of chaining the
            # sample; the step after a lane's last planned token chains the
            # freshly sampled first generation — prefill→decode transition
            # happens on device, mid-scan.
            def body(carry, xs):
                kv, toks, pos = carry
                keys = make_slot_keys(base_key, seeds, steps0 + xs["k"])
                tok_in = jnp.where(xs["pm"], xs["pt"], toks)
                logits, kv = self.model_mod.decode_forward(
                    params, kv, tok_in, pos, block_tables, statics)
                toks2, logprobs = sample_tokens(logits, keys, temperature,
                                                top_k, top_p)
                return (kv, toks2, pos + 1), (toks2, logprobs)

            if K == 1:
                # one step needs no loop around it
                (kv, _, _), (toks, logprobs) = body(
                    (kv, tokens, positions),
                    {"k": 0, "pt": planned[0], "pm": planned_mask[0]})
                return toks[None], logprobs[None], kv
            (kv, _, _), (toks_k, logprobs_k) = jax.lax.scan(
                body, (kv, tokens, positions),
                {"k": jnp.arange(K), "pt": planned, "pm": planned_mask})
            return toks_k, logprobs_k, kv

        self._decode_k_jit = jax.jit(jax.named_scope("decode")(decode_k),
                                     donate_argnums=(1,))
        # device-resident zeros reused by every dispatch with no active
        # lane (the overwhelmingly common case)
        self._planned_zero = (jnp.zeros((K, self.cfg.max_num_seqs),
                                        jnp.int32),
                              jnp.zeros((K, self.cfg.max_num_seqs), bool))
        # deferred-harvest input merge: slots that continue from the
        # in-flight dispatch chain its last device tokens ([K, B] → row
        # K-1), every other slot feeds its host value
        self._merge_jit = jax.jit(
            lambda dev_k, host, mask: jnp.where(mask, dev_k[-1], host))

        # unified ragged dispatch (engine/ragged.py +
        # docs/ragged_attention.md): ONE program serves a flat
        # [ragged_max_tokens] mixed prefill+decode token batch — each
        # slot's contiguous row span scatters its KV and attends masked
        # at its own positions (per-row the decode program's exact
        # math), and each slot samples from its LAST row's logits with
        # the same per-(seed, key_step) key discipline the split
        # programs use. One compiled shape serves every batch mix, so
        # the per-bucket prefill program family never compiles when
        # ragged serving is on.
        #
        # spec_k > 0 compiles the ROW-SAMPLED variant instead (still
        # exactly ONE program): logits and a sample for EVERY token
        # row, each row keyed at its slot's key_step + row offset —
        # the verify program's lockstep-PRNG discipline riding the
        # ragged batch, so speculative spans verify in the same
        # dispatch as prefill chunks and plain decode rows. At the
        # sample row of a non-spec span the key (and hence the token)
        # is identical to the slot-sampled variant by construction:
        # row r of a span keys at key_step + r, the last row at
        # key_step + len - 1 — the lane skew convention.
        self._ragged_jit = None
        self._ragged_row_sampled = False
        if self.cfg.ragged_dispatch:
            Lmax = self.cfg.ragged_max_seq_rows
            self._ragged_row_sampled = self.cfg.spec_k > 0

            if self._ragged_row_sampled:
                def ragged(params, kv, tokens, positions, tables,
                           row_slot, seq_starts, seq_counts,
                           sample_rows, seeds, steps, temperature,
                           top_k, top_p):
                    # steps is [capacity] ROW steps here; the other
                    # sampling params stay per-slot and gather through
                    # row_slot (the trailing trash slot holds zeros)
                    params = unpack_params(params)
                    logits, kv = self.model_mod.ragged_forward(
                        params, kv, tokens, positions, tables,
                        row_slot, seq_starts, seq_counts, sample_rows,
                        statics, max_rows=Lmax, sample_all_rows=True)
                    keys = make_slot_keys(
                        seed, jnp.take(seeds, row_slot), steps)
                    toks, logprobs = sample_tokens(
                        logits, keys,
                        jnp.take(temperature, row_slot),
                        jnp.take(top_k, row_slot),
                        jnp.take(top_p, row_slot))
                    return toks, logprobs, kv
            else:
                def ragged(params, kv, tokens, positions, tables,
                           row_slot, seq_starts, seq_counts,
                           sample_rows, seeds, steps, temperature,
                           top_k, top_p):
                    params = unpack_params(params)
                    logits, kv = self.model_mod.ragged_forward(
                        params, kv, tokens, positions, tables,
                        row_slot, seq_starts, seq_counts, sample_rows,
                        statics, max_rows=Lmax)
                    keys = make_slot_keys(seed, seeds, steps)
                    toks, logprobs = sample_tokens(logits, keys,
                                                   temperature, top_k,
                                                   top_p)
                    return toks, logprobs, kv

            self._ragged_jit = jax.jit(ragged, donate_argnums=(1,))
            # pipelined-dispatch chained-sample merge (ragged form):
            # chained rows take the PREVIOUS dispatch's device token at
            # their slot's recorded sample row; everything else feeds
            # host values. jnp.take covers both variants ([S] slot
            # toks index by slot, [capacity] row toks by sample row).
            self._ragged_merge_jit = jax.jit(
                lambda prev, srows, host, mask: jnp.where(
                    mask, jnp.take(prev, srows), host))

        # speculative verify (engine/spec/, docs/speculative.md): score
        # Tv = spec_k+1 positions per slot in ONE dispatch by flattening
        # [B, Tv] query rows through the SAME paged decode forward.
        # decode_forward scatters each row's input-token KV before
        # attention and row (b, t) attends positions <= pos_b + t, so
        # the rows of one sequence score its draft chain causally —
        # parallel scoring at ~one batched step's weight read instead of
        # Tv sequential steps. Per-position keys are LOCKSTEP with plain
        # decode (steps0 + t == the key_step decode would use at that
        # stream index), so sampled row t is bit-identical to what
        # non-speculative decode would emit there; acceptance is then
        # host-side token equality (spec.accept_lockstep).
        self._verify_jit = None
        if self.cfg.spec_k > 0:
            Tv = self.cfg.spec_k + 1

            def per_row(tokens, positions, block_tables, seeds, steps0,
                        temperature, top_k, top_p):
                """A [B, Tv] step's arguments a row: → (tokens, positions,
                tables, each row a sequence of its own; ``sample``: logits
                [B·Tv, V] → (tokens, logprobs) under the lockstep keys)."""
                B = tokens.shape[0]
                t_off = jnp.arange(Tv, dtype=jnp.int32)
                keys = make_slot_keys(
                    seed, jnp.repeat(seeds, Tv),
                    (steps0[:, None]
                     + t_off.astype(steps0.dtype)[None, :]).reshape(B * Tv))

                def sample(logits):
                    return sample_tokens(
                        logits, keys, jnp.repeat(temperature, Tv),
                        jnp.repeat(top_k, Tv), jnp.repeat(top_p, Tv))
                return (tokens.reshape(B * Tv),
                        (positions[:, None] + t_off[None, :]).reshape(B * Tv),
                        jnp.repeat(block_tables, Tv, axis=0), sample)

            def verify(params, kv, tokens, positions, block_tables,
                       seeds, steps0, temperature, top_k, top_p):
                B = tokens.shape[0]
                flat_tokens, flat_pos, flat_tables, sample = per_row(
                    tokens, positions, block_tables, seeds, steps0,
                    temperature, top_k, top_p)
                logits, kv = self.model_mod.decode_forward(
                    unpack_params(params), kv, flat_tokens, flat_pos,
                    flat_tables, statics)
                toks, logprobs = sample(logits)
                return (toks.reshape(B, Tv), logprobs.reshape(B, Tv),
                        kv)

            if self.resident_drafter:
                def decode_mtp(params, kv, tokens, positions, block_tables,
                               seeds, steps0, temperature, top_k, top_p,
                               carry=None, mask=None):
                    """The two-row step of a resident drafter, in verify's
                    shape: rows (last token, draft) of every slot at pos,
                    pos + 1 through every layer and both pools, sampled
                    with the lockstep keys; then the module over both rows
                    with the sampled tokens → (tokens [B, 2], logprobs
                    [B, 2], kv, drafts [B, 2]: the guess at the token after
                    each row's sample).

                    The loop's form takes the ``carry`` of the dispatch
                    before it and a per-slot ``mask``, and returns its own
                    behind the drafts: (adv [B]: 2 where row 0's sample is
                    the draft that row 1 scored, else 1; the accepted
                    row's sample; the draft behind it). A masked slot's rows
                    are the carry's pair, adv positions and key steps beyond
                    the host's; any other slot's are the host's. Acceptance
                    and rewind are decided here, so a step can be queued
                    behind one whose tokens no one has fetched. A caller
                    that drives one step at a time passes neither."""
                    B = tokens.shape[0]
                    if carry is not None:
                        adv, last, draft = carry
                        ahead = jnp.where(mask, adv, 0)
                        tokens = jnp.where(mask[:, None],
                                           jnp.stack([last, draft], axis=1),
                                           tokens)
                        positions = positions + ahead.astype(positions.dtype)
                        steps0 = steps0 + ahead.astype(steps0.dtype)
                    flat_tokens, flat_pos, flat_tables, sample = per_row(
                        tokens, positions, block_tables, seeds, steps0,
                        temperature, top_k, top_p)
                    toks, logprobs, draft_logits, kv = \
                        self.model_mod.decode_forward_mtp(
                            unpack_params(params), kv, flat_tokens, flat_pos,
                            flat_tables, statics, sample, rows=Tv)
                    toks = toks.reshape(B, Tv)
                    drafts = jnp.argmax(draft_logits, axis=-1).astype(
                        jnp.int32).reshape(B, Tv)
                    out = (toks, logprobs.reshape(B, Tv), kv, drafts)
                    if carry is None:
                        return out
                    took = (toks[:, 0] == tokens[:, 1]).astype(jnp.int32)
                    return (*out, (1 + took,
                                   jnp.take_along_axis(
                                       toks, took[:, None], axis=1)[:, 0],
                                   jnp.take_along_axis(
                                       drafts, took[:, None], axis=1)[:, 0]))

                verify = jax.named_scope("decode")(decode_mtp)
                # a fresh step's carry: nothing chained (cached on the
                # device, as _planned_zero)
                slots = (self.cfg.max_num_seqs,)
                self._carry_zero = (
                    tuple(jnp.zeros(slots, jnp.int32) for _ in range(3)),
                    jnp.zeros(slots, bool))

            self._verify_jit = jax.jit(verify, donate_argnums=(1,))

        # sequence-parallel long-prompt prefill (ring attention over "sp")
        self._prefill_sp_jit = None
        self._sp = 1
        if self.mesh is not None and self.mesh.shape.get("sp", 1) > 1:
            self._sp = self.mesh.shape["sp"]
            mesh = self.mesh

            def prefill_sp(params, kv, tokens, block_table, true_len,
                           key, temperature, top_k, top_p):
                params = unpack_params(params)
                logits, kv = self.model_mod.prefill_forward_sp(
                    params, kv, tokens, block_table, true_len, statics, mesh)
                tok, logprob = sample_tokens(
                    logits[None, :], key[None], temperature[None],
                    top_k[None], top_p[None])
                return tok[0], logprob[0], kv

            self._prefill_sp_jit = jax.jit(prefill_sp, donate_argnums=(1,))

    # ------------------------------------------------------------ lifecycle
    def ensure_started(self) -> None:
        if self._dead is not None:
            # a fatal loop error already failed every pending request;
            # silently restarting would re-serve them (round-5 review)
            raise RuntimeError(
                f"engine loop died: {self._dead!r} — create a new "
                f"EngineCore") from self._dead
        if self._loop_task is None or self._loop_task.done():
            self._stopping = False
            # worker-thread hooks (disk-evict → remote promotion) need a
            # handle to reach the loop via call_soon_threadsafe
            self._loop = asyncio.get_running_loop()
            # a fresh wakeup event per (re)start: asyncio primitives
            # loop-bind on first wait, and a core restarted on a NEW
            # loop (module-scoped test fixtures, embedders re-running
            # asyncio.run) would otherwise die on the old loop's event
            self._work_event = asyncio.Event()
            self._loop_task = self._loop.create_task(
                self._run_loop(), name="engine-core-loop")
            self.flight.start_lag_probe()

    @property
    def running(self) -> bool:
        """The engine loop is a live task (of the event loop that made
        the first request)."""
        return self._loop_task is not None and not self._loop_task.done()

    async def stop(self) -> None:
        if self._stopping and self._loop_task is None:
            # stopped already, maybe on another event loop (launch/run.py
            # run_http stops the engine on the loop that served it, and
            # the launcher's own stop comes after): the tiers are flushed
            # and closed, and nothing of this engine is on this loop.
            # Still one turn of the caller's loop, as every stop takes:
            # what the loop's running callback held of a cancelled
            # run_http task (its traceback, hence the engine) goes with it
            await asyncio.sleep(0)
            return
        self._stopping = True
        self.flight.stop_lag_probe()
        self._work_event.set()
        if self._loop_task is not None:
            try:
                await asyncio.wait_for(self._loop_task, timeout=5)
            except asyncio.TimeoutError:
                self._loop_task.cancel()
            except asyncio.CancelledError:
                # wait_for re-raises the LOOP task's cancellation (process
                # shutdown cancels every task) — that alone must not
                # abort stop(): the remaining cleanup (incl. the host→
                # disk flush) is the point of a graceful stop. Only
                # re-raise when stop() itself was cancelled.
                if not self._loop_task.done():
                    raise
            except Exception:  # noqa: BLE001 — fatal loop death is a
                # supported state (_fail_pending already failed every
                # pending request and logged the exception); stop()'s
                # remaining cleanup must still run
                pass
            self._loop_task = None
        if self._admissions:              # finish deferred admissions
            self._complete_admissions()
        if self._onboard_tasks:           # in-flight onboard preps
            for t in list(self._onboard_tasks):
                t.cancel()
            await asyncio.gather(*list(self._onboard_tasks),
                                 return_exceptions=True)
        if self._stream_tasks:            # in-flight layer-stream onboards
            for t in list(self._stream_tasks):
                t.cancel()
            await asyncio.gather(*list(self._stream_tasks),
                                 return_exceptions=True)
        if self._onboards:                # release reserved onboard blocks
            for req, slot, plan, _prepped, _rvals in self._onboards:
                self.slots[slot] = None
                self.kv_manager.pool.release(plan.all_blocks)
                self.kv_manager.host_pool.unpin(plan.host_slots)
                if plan.disk_hashes:
                    self.disk_store.unpin(plan.disk_hashes)
                if plan.remote_hashes:
                    self.remote_store.unpin(plan.remote_hashes)
                self._finish_request(req, FinishReason.CANCELLED)
            self._onboards = []
        if self._pending is not None:     # drain the in-flight dispatch
            self._drain("stop")
        if self._ragged_pending is not None:  # the ragged form of same
            prev, self._ragged_pending = self._ragged_pending, None
            self._harvest_ragged(prev)
        if self.offload_engine is not None:
            await self.offload_engine.stop()
        if self.spill_engine is not None:
            # graceful persist: everything still host-resident goes to
            # disk so the next engine pointed at kv_disk_dir warm-starts
            # with the full working set (kill -9 keeps only what the
            # write-behind pump had already acknowledged)
            try:
                await asyncio.wait_for(self.flush_host_to_disk(),
                                       timeout=30)
            except asyncio.TimeoutError:
                logger.warning("host→disk flush timed out on stop")
            await self.spill_engine.stop()
            self.disk_store.close()
        if self.remote_spill_engine is not None:
            # drain AFTER the disk pump: the flush above may have forced
            # disk evictions whose promotion jobs are still queued
            await self.remote_spill_engine.stop()
            self.remote_store.close()

    @property
    def host_stall_s(self) -> float:
        """Seconds the loop has blocked on device→host fetches (harvests
        and admission token fetches): the clock's ``wait`` total, measured
        not modelled — a copy that already landed reads ~0. Sampled around
        a latency window by tools/serve_bench.py."""
        return self.clock.seconds["wait"]

    @property
    def wire_kv_heads(self) -> int:
        """Head count for the head-major KV wire format (block_copy
        to/from_wire_format): int8 pools and MLA latent pools ship whole
        rows as ONE opaque "head" (in-row scales / latent+rope lanes
        have no head structure to split), so handoff/offload round trips
        are bit-exact; full-precision llama pools use the real KV head
        count (which the dst-tp>src-tp reshard slices per rank)."""
        return (1 if self.cfg.kv_quantization != "none" or self.is_mla
                else self.model_cfg.num_kv_heads)

    def _check_kv_payload_layout(self, lanes: int, dtype,
                                 kind: str) -> None:
        """A disagg KV payload must match this pool's row layout exactly:
        same lane width (int8 rows bundle their tp-shard scale groups, so
        width also encodes the prefill engine's tp) and same dtype.
        DEVICE-plane payloads with a differing kv_quantization were
        already repacked (_maybe_repack_kv_payload) before this check;
        anything still mismatched here — wire-plane cross-quant, int8
        across differing tp — fails loudly."""
        pool = next(iter(self.kv.values()))   # key-agnostic: llama
        # pools are {"k","v"}, MLA latent pools are {"kv"}
        if lanes != pool.shape[-1] or np.dtype(dtype) != pool.dtype:
            raise ValueError(
                f"disagg {kind} KV payload layout mismatch: payload rows "
                f"have {lanes} lanes of {np.dtype(dtype)}, this pool has "
                f"{pool.shape[-1]} lanes of {pool.dtype} — prefill and "
                f"decode engines must share kv_quantization (and tp, for "
                f"int8 pools)")

    def _check_layer_stream_layout(self, manifest) -> None:
        """Layer-stream manifests announce geometry before any bulk
        frame: per-layer wire shape [H, n, bs, D] plus layer count and
        dtype — validated against the pool like a monolithic payload,
        plus the layer axis (a stream describing a different depth could
        otherwise scatter past the pool's layer extent)."""
        import ml_dtypes  # noqa: F401 — registers bf16 et al. for np.dtype
        h, _n, bs, d = (manifest.shape + [0, 0, 0, 0])[:4]
        self._check_kv_payload_layout(h * d, manifest.dtype, "wire")
        pool = next(iter(self.kv.values()))
        if manifest.num_layers != pool.shape[0]:
            raise ValueError(
                f"disagg wire KV payload layout mismatch: layer stream "
                f"announces {manifest.num_layers} layers, this pool has "
                f"{pool.shape[0]}")
        if bs != self.cfg.kv_block_size:
            raise ValueError(
                f"disagg wire KV payload layout mismatch: layer stream "
                f"block size {bs} != pool block size "
                f"{self.cfg.kv_block_size}")

    def _maybe_repack_kv_payload(self, pc):
        """Scale-aware repack of a DEVICE-plane disagg payload whose
        kv_quantization differs from this pool's (round 5, VERDICT r4
        item 4; reference analog: block_copy.cu's cross-layout reshard,
        lib/llm/src/kernels/block_copy.cu:558-728): int8 payload rows
        dequantize, bf16 rows requantize into THIS pool's group/section
        layout — all on device, before admission. Same-layout payloads
        pass through untouched (bit-exact as before). Still refused:
        int8 payloads whose tp-shard GROUP COUNT differs from this
        pool's (a group re-split must reshuffle head ownership), and
        every wire-plane mismatch (the wire is the compatibility
        fallback; its head-major format carries no scale structure to
        convert in place)."""
        import jax.numpy as jnp

        from ..engine.attention import (dequant_kv_rows,
                                        dequant_kv_rows_sections,
                                        kv_row_groups, quantize_kv_rows,
                                        quantize_kv_rows_sections)
        pool = next(iter(self.kv.values()))
        want_w, want_dt = pool.shape[-1], pool.dtype
        sample = next(iter(pc.stacked.values()))
        have_w, have_dt = sample.shape[-1], sample.dtype
        if have_w == want_w and have_dt == want_dt:
            return pc
        src_q = have_dt == jnp.int8
        dst_q = want_dt == jnp.int8
        if not (src_q or dst_q):
            return pc          # width-only mismatch: the tp reshard path
        if self.is_mla:
            sections = (self.model_cfg.kv_lora_rank,
                        self.model_cfg.qk_rope_head_dim)
            C = sum(sections)
        else:
            sections = None
            C = self.model_cfg.num_kv_heads * self.model_cfg.head_dim
        if src_q and dst_q:
            raise ValueError(
                f"disagg KV repack across two int8 layouts ({have_w} -> "
                f"{want_w} lanes) is not supported: the scale GROUP "
                f"counts encode each engine's tp, and re-splitting "
                f"groups must reshuffle head ownership")

        def convert(arr):
            lead = arr.shape[:-1]
            rows = arr.reshape((-1, arr.shape[-1]))
            if src_q:
                mid = jnp.bfloat16 if dst_q else want_dt
                rows = (dequant_kv_rows_sections(rows, sections, mid)
                        if sections is not None
                        else dequant_kv_rows(rows, C, mid))
            if dst_q:
                x = rows[..., :C].astype(jnp.bfloat16)
                rows = (quantize_kv_rows_sections(x, sections)
                        if sections is not None
                        else quantize_kv_rows(
                            x, kv_row_groups(want_w, C)))
            return rows.reshape(lead + (rows.shape[-1],))

        # jit per payload layout (ADVICE r5): the eager version walked
        # every row un-fused on the event loop; the jitted dispatch
        # returns immediately and the caller awaits readiness off-loop
        key = (have_w, str(have_dt))
        fn = self._repack_jits.get(key)
        if fn is None:
            fn = jax.jit(convert)
            self._repack_jits[key] = fn
        import dataclasses as _dc
        new_stacked = {k: fn(v) for k, v in pc.stacked.items()}
        logger.info("disagg KV payload repacked %s/%d -> %s/%d lanes "
                    "for %s", have_dt, have_w, want_dt,
                    new_stacked[next(iter(new_stacked))].shape[-1],
                    pc.request_id)
        return _dc.replace(pc, stacked=new_stacked)

    # ------------------------------------------------------------- frontend
    async def submit(self, req: EngineRequest) -> None:
        if self.beside_paged_rows and (
                req.precomputed is not None or req.handoff is not None
                or req.handoff_device):
            # raised here, to the caller, not inside the engine loop
            raise NotImplementedError(
                "disaggregated prefill/decode hand-off ships paged rows "
                "only; it is not implemented with "
                + " and ".join(self.beside_paged_rows))
        if req.precomputed is not None:
            # validate the payload layout HERE, synchronously: the caller
            # gets the error; a raise inside the engine loop's admission
            # path would kill the loop and hang every in-flight request
            from ..llm.kv_transport import DeviceKvPayload
            pc = req.precomputed
            if isinstance(pc, DeviceKvPayload):
                repacked = self._maybe_repack_kv_payload(pc)
                if repacked is not pc:
                    # await device completion in an executor so a long
                    # cross-quant repack never stalls the event loop (and
                    # with it the in-flight decode schedule) — ADVICE r5
                    await asyncio.to_thread(
                        jax.block_until_ready,
                        list(repacked.stacked.values()))
                req.precomputed = pc = repacked
                sample = next(iter(pc.stacked.values()))
                self._check_kv_payload_layout(sample.shape[-1],
                                              sample.dtype, "device")
            else:
                from ..llm.kv.stream import LayerStreamPayload
                if isinstance(pc, LayerStreamPayload):
                    # layer stream: the manifest announced the geometry
                    # up front — validate before any frame is scattered
                    self._check_layer_stream_layout(pc.manifest)
                else:
                    sample = next(iter(pc.values.values()))
                    self._check_kv_payload_layout(
                        sample.shape[1] * sample.shape[4], sample.dtype,
                        "wire")
        if req.trace is None:
            # bind the ambient request trace (frontend-opened for
            # in-process pipelines, ingress-opened child for the request
            # plane) so engine phases land in the fleet tree
            from ..runtime.tracing import current_trace
            req.trace = current_trace()
        self.ensure_started()
        self._inflight_reqs[id(req)] = req
        await self.waiting.put(req)
        self._work_event.set()

    def reannounce_kv(self) -> int:
        """Replay every stored-block announcement into the KV event
        publisher — the lease-reclaim recovery hook (KNOWN_ISSUES
        kv-router staleness): after a transient lease expiry the router
        wiped this worker's radix index; the reclaim replays discovery
        keys but not content events, so the pool re-announces them."""
        if self.kv_event_publisher is None:
            return 0
        n = self.kv_manager.pool.reannounce(
            self.kv_event_publisher.publish_stored)
        # disk (G3) bring-up: a warm-started store holds prefixes the
        # device pool has never seen — announce them tier-tagged so the
        # router's radix index can route matching prompts here for a
        # promote instead of a cold recompute elsewhere
        if self.disk_store is not None:
            for h, th, ph in self.disk_store.registered_entries():
                if not self.kv_manager.pool.peek_prefix([h]):
                    self.kv_event_publisher.publish_stored(
                        -1, h, th, ph, tier="disk")
                    n += 1
        # remote (G4) object tier: durable blocks THIS worker can fetch
        # back (peer-held hashes are the peer's to announce)
        if self.remote_store is not None:
            for h, th, ph in self.remote_store.registered_entries():
                if (not self.kv_manager.pool.peek_prefix([h])
                        and not (self.disk_store is not None
                                 and self.disk_store.contains(h))):
                    self.kv_event_publisher.publish_stored(
                        -1, h, th, ph, tier="remote")
                    n += 1
        return n

    async def flush_host_to_disk(self) -> int:
        """Persist every host-resident block to the disk tier NOW and
        wait for the writes to be acknowledged (fsync'd manifest) — the
        llmctl ``kv flush`` barrier, also run on graceful stop(). Returns
        the number of blocks newly offered to the spill queue."""
        if self.spill_engine is None:
            return 0
        from ..llm.kv.diskstore import SpillJob
        host = self.kv_manager.host_pool
        n = 0
        for h, th, ph, slot in host.resident_entries():
            if self.disk_store.contains(h):
                continue
            if self.spill_engine.offer(SpillJob(
                    seq_hash=h, tokens_hash=th, parent_hash=ph,
                    values=host.row_copy(slot))):
                n += 1
        await self.spill_engine.drain()
        return n

    def _dma_copies_per_wave(self) -> float:
        """Decode-DMA issues per wave over the CURRENT batch state — the
        host-side mirror of the kernel's wave walk (attention.
        dma_copy_counts), fed to nv_llm_kv_attn_dma_copies_per_wave.
        chunk× on a fully fragmented pool, 1-2 on a contiguous one."""
        from .attention import dma_copy_counts
        seq_lens = np.where(
            np.array([s is not None and s.ready for s in self.slots]),
            self._positions + 1, 0).astype(np.int32)
        if not seq_lens.any():
            return 0.0
        counts = dma_copy_counts(
            self._block_tables[:, :self.M], seq_lens,
            block_size=self.cfg.kv_block_size,
            pool_blocks=self.cfg.num_kv_blocks,
            dual_stream=not self.is_mla,
            coalesce=self.cfg.kv_contig_alloc)
        return counts["copies_per_wave"]

    def metrics(self) -> ForwardPassMetrics:
        active = sum(1 for s in self.slots if s is not None)
        total_blocks = self.cfg.num_kv_blocks - 1
        used = self.kv_manager.pool.used_blocks
        host = self.kv_manager.host_pool
        disk = self.disk_store
        pool = self.kv_manager.pool
        tier_kw = {
            "kv_frag_ratio": pool.frag_ratio(),
            "kv_contig_runs": pool.contig_runs,
            "kv_contiguity_ratio": pool.contiguity_ratio(),
            "kv_defrag_moves_total": pool.defrag_moves_total,
            "attn_dma_copies_per_wave": self._dma_copies_per_wave(),
        }
        if host is not None:
            tier_kw.update(
                host_stored_total=host.stored_blocks_total,
                host_evicted_total=host.evicted_blocks_total,
                host_hit_rate=host.hit_rate())
        if self.offload_engine is not None:
            tier_kw.update(offload_dropped_jobs_total=self
                           .offload_engine.dropped_jobs_total)
        if self.cfg.ragged_dispatch:
            # ragged dispatch (docs/ragged_attention.md): how full each
            # unified dispatch runs, how often prefill and decode share
            # one, and the split-path dispatches the packing saved
            tier_kw.update(
                ragged_fill_ratio=(
                    self.ragged_rows_total
                    / (self.ragged_dispatches
                       * self.cfg.ragged_max_tokens)
                    if self.ragged_dispatches else 0.0),
                ragged_mixed_ratio=(
                    self.ragged_mixed_dispatches / self.ragged_dispatches
                    if self.ragged_dispatches else 0.0),
                ragged_dispatches_saved_total=self.ragged_dispatches_saved,
                # cross-sequence wave prefetch: first waves a
                # predecessor's last wave covered (host mirror of the
                # kernel's parity chain) / draft rows that rode ragged
                ragged_prefetch_hit_ratio=(
                    self.ragged_prefetched_waves
                    / self.ragged_first_waves
                    if self.ragged_first_waves else 0.0),
                ragged_spec_rows_total=self.ragged_spec_rows)
        if self.pp > 1:
            from ..parallel.pipeline_parallel import (
                pp_bubble_fraction, pp_dispatch_utilization)
            K = self.cfg.decode_steps_per_dispatch
            tier_kw.update(
                pp_stages=self.pp,
                pp_microbatch=self.B // self.pp,
                pp_utilization=pp_dispatch_utilization(self.pp, K),
                pp_bubble_fraction=pp_bubble_fraction(self.pp, K))
        if disk is not None:
            tier_kw.update(
                disk_used_blocks=disk.used_blocks,
                disk_capacity_blocks=disk.capacity,
                disk_stored_total=disk.stored_blocks_total,
                disk_evicted_total=disk.evicted_blocks_total,
                disk_hit_rate=disk.hit_rate(),
                disk_bytes_used=disk.bytes_used,
                disk_spill_dropped_total=self
                .spill_engine.dropped_jobs_total,
                disk_spill_shed_total=self
                .spill_engine.shed_writes_total)
        if self.remote_store is not None or self.kv_fabric is not None:
            # remote (G4) fabric: tier occupancy + the measured link
            # model the router's NetKV scoring consumes (kv_router/
            # scoring.py network_adjusted_overlap)
            tier_kw.update(prefill_published_blocks_total=self
                           .prefill_published_blocks)
            if self.kv_fabric is not None:
                tier_kw.update(self.kv_fabric.metrics())
            else:
                rs = self.remote_store
                tier_kw.update(
                    remote_used_blocks=rs.used_blocks,
                    remote_capacity_blocks=rs.capacity,
                    remote_peer_blocks=rs.peer_block_count(),
                    remote_stored_total=rs.stored_blocks_total,
                    remote_hit_rate=rs.hit_rate(),
                    remote_fetch_failures_total=rs.fetch_failures_total,
                    remote_admission_rejects_total=rs
                    .admission_rejects_total)
        if self.tenant_admitted:
            # per-tenant serving stats (llm/tenancy.py; the
            # nv_llm_tenant_* labeled-gauge feed): admitted requests,
            # resident KV blocks across tiers, and prefix hit rate
            ledger = self.tenancy
            tier_kw["tenant_stats"] = {
                t: {"admitted": n,
                    "throttled": 0,
                    "kv_blocks": (ledger.blocks(t)
                                  if ledger is not None else 0),
                    "hit_rate": (self.tenant_hits.get(t, 0)
                                 / max(self.tenant_queries.get(t, 0), 1))}
                for t, n in sorted(self.tenant_admitted.items())}
        _stream_wall = (self.disagg_stream_hidden_s
                        + self.disagg_stream_exposed_s)
        tier_kw.update(
            # streaming layer-wise KV handoff (llm/kv/stream.py): the
            # nv_llm_disagg_stream_* gauge feed. disagg_stream_layers is
            # the MEASURED streaming depth the router's overlap credit
            # prices with (scoring.network_adjusted_overlap) — 0 until
            # the first streamed admission proves the plane is live.
            disagg_stream_layers_total=self.disagg_stream_layers_scattered,
            disagg_stream_fallbacks_total=self.disagg_stream_fallbacks,
            disagg_stream_overlap_ratio=(
                self.disagg_stream_hidden_s / _stream_wall
                if _stream_wall > 0 else 0.0),
            disagg_stream_layers=(
                self.model_cfg.num_layers
                if self.disagg_stream_admits > 0 else 0))
        from ..runtime.tracing import tracer as _tracer
        return ForwardPassMetrics(
            requests_cancelled_total=self.requests_cancelled_total,
            requests_deadline_exceeded_total=self
            .requests_deadline_exceeded_total,
            kv_bytes_per_block=self.kv_bytes_per_block(),
            kv_block_size=self.cfg.kv_block_size,
            kv_state_bytes=self.B * self._step_state_bytes // 2,
            prefill_tok_per_s=self.measured_prefill_tok_per_s(),
            trace_dropped_log_lines_total=_tracer.dropped_log_lines,
            loop_lag_ms=self.flight.loop_lag_ms, **self.flight.metrics_kw(),
            loop_lag_max_ms=self.flight.loop_lag_max_ms,
            **tier_kw,
            request_active_slots=active,
            request_total_slots=self.B,
            kv_active_blocks=used,
            kv_total_blocks=total_blocks,
            num_requests_waiting=self.waiting.qsize(),
            gpu_cache_usage_perc=used / max(total_blocks, 1),
            gpu_prefix_cache_hit_rate=self.kv_manager.pool.hit_rate(),
            spec_drafted_total=self.spec_drafted_tokens,
            spec_accepted_total=self.spec_accepted_tokens,
            spec_rewound_rows_total=self.spec_rewound_rows,
            spec_acceptance_rate=(
                self.spec_accepted_tokens / self.spec_drafted_tokens
                if self.spec_drafted_tokens else 0.0),
            spec_accepted_per_step=(
                self.spec_accepted_tokens / self.spec_dispatches
                if self.spec_dispatches else 0.0),
        )

    # ------------------------------------------------------------ scheduler
    def _free_slot_index(self) -> int:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return -1

    def _blocks_needed(self, n_tokens: int) -> int:
        bs = self.cfg.kv_block_size
        return (n_tokens + bs - 1) // bs

    async def _run_loop(self) -> None:
        try:
            await self._run_loop_inner()
        except asyncio.CancelledError:
            raise
        except Exception as e:   # noqa: BLE001 — fatal loop error
            # Round-5 postmortem: an exception here used to kill the
            # loop task SILENTLY, leaving every pending request awaiting
            # an out_queue forever (observed as a test hang, not a
            # failure). Fail them all loudly instead, then re-raise.
            logger.exception("engine loop died; failing %d active + %d "
                             "waiting requests", 
                             sum(1 for x in self.slots if x is not None),
                             self.waiting.qsize())
            self._fail_pending(e)
            raise

    def _fail_pending(self, exc: BaseException) -> None:
        from ..llm.protocols.common import FinishReason
        self._dead = exc
        for rid, req in list(self._inflight_reqs.items()):
            req.out_queue.put_nowait((FINISH_SENTINEL,
                                      FinishReason.ERROR))
        self._inflight_reqs.clear()
        # free every admitted request's KV allocation (ADVICE r5): the
        # core itself is unrecoverable (_dead gates ensure_started), but
        # the pool object may outlive it — a recovery path that rebuilds
        # the loop around the same kv_manager must not inherit leaked
        # refcounts. Slot release, not _release_slot: no offload
        # write-back or sampler-state care is owed to a dead loop.
        for req in self.slots:
            if req is not None and req.blocks:
                self.kv_manager.pool.release(req.blocks)
                req.blocks = []
        for req, _slot, plan, _prepped, _rvals in self._onboards:
            self.kv_manager.pool.release(plan.all_blocks)
            if self.kv_manager.host_pool is not None:
                self.kv_manager.host_pool.unpin(plan.host_slots)
            if plan.disk_hashes and self.disk_store is not None:
                self.disk_store.unpin(plan.disk_hashes)
            if plan.remote_hashes and self.remote_store is not None:
                self.remote_store.unpin(plan.remote_hashes)
        self._onboards = []
        # clear scheduler state so nothing can be re-served even if a
        # caller pokes internals
        self.slots = [None] * len(self.slots)
        while not self.waiting.empty():
            try:
                self.waiting.get_nowait()
            except asyncio.QueueEmpty:
                break

    async def _run_loop_inner(self) -> None:
        # the loop task is created from the FIRST submit()'s context and
        # would inherit that request's ambient trace forever — detach;
        # per-request trace identity rides EngineRequest.trace instead
        from ..runtime.tracing import detach_trace
        detach_trace()
        logger.info("engine loop starting: %d slots, %d KV blocks, block=%d",
                    self.B, self.cfg.num_kv_blocks, self.cfg.kv_block_size)
        clock = self.clock
        while not self._stopping:
            progressed = False
            clock.enter("sweep")
            # 0) opportunistic KV compaction: only when no admission is
            # queued and no dispatch is un-harvested (the pass inserts
            # one small device copy ahead of the next decode dispatch).
            # The one-step path always has a step in flight while it
            # decodes: the pass looks anyway and, if it finds a move,
            # harvests that step first
            if (self.waiting.empty() and self._ragged_pending is None
                    and (self._pending is None
                         or self._pending["K"] == 1)):
                self._maybe_defrag()
            # 0.5) cancellation/deadline sweep: vacate slots and purge
            # the waiting queue for requests whose client stopped caring
            # — one loop tick, no waiting for the next emit
            if self._sweep_cancelled():
                progressed = True
            # 1) admit waiting work into free slots
            clock.enter("admit")
            while not self.waiting.empty():
                slot = self._free_slot_index()
                if slot < 0:
                    break
                req: EngineRequest = self.waiting.get_nowait()
                if req.cancelled:
                    self._finish_request(req, FinishReason.CANCELLED)
                    continue
                if not self._try_admit(req, slot):
                    # not enough KV blocks — put it back and stop admitting
                    self.waiting._queue.appendleft(req)  # type: ignore[attr-defined]
                    break
                progressed = True
            # 2) run one decode step for whatever is active and ready
            # (the step functions and harvests mark build / dispatch /
            # wait / post themselves)
            if any(s is not None and s.ready for s in self.slots):
                clock.enter("build")
                self._decode_step()
                progressed = True
            elif self._pending is not None:
                # all requests finished mid-harvest with a chained dispatch
                # still in flight: drain it so the dead requests and device
                # buffers don't sit retained across an idle period
                self._drain("idle")
                progressed = True
            elif self._ragged_pending is not None:
                # same drain for a pipelined ragged dispatch
                prev, self._ragged_pending = self._ragged_pending, None
                self._harvest_ragged(prev)
                progressed = True
            # 3) deferred admissions: their async fetch overlapped step 2
            clock.enter("complete")
            if self._admissions:
                self._complete_admissions()
                progressed = True
            # 4) host-tier onboards whose off-thread prep finished
            if self._onboards:
                self._complete_onboards()
                progressed = True
            clock.enter("yield")
            if not progressed:
                self._work_event.clear()
                try:
                    await asyncio.wait_for(self._work_event.wait(), timeout=0.5)
                except asyncio.TimeoutError:
                    pass
            else:
                await self._yield_until_quiet()
        logger.info("engine loop stopped")

    async def _yield_until_quiet(self) -> None:
        """Let producers and consumers run until the shared event loop is
        quiet: one iteration polls the sockets once and runs what was
        ready, and what those callbacks schedule runs in the next, so a
        chain of hops (a new connection up to its ``submit()``) crosses
        inside this yield, not one engine cycle a hop. At most
        ``YIELD_DRAIN_MAX_ITERS`` iterations."""
        for _ in range(YIELD_DRAIN_MAX_ITERS):
            await asyncio.sleep(0)
            self.clock.yield_iters += 1
            if _loop_is_quiet(self._loop):
                break

    # --------------------------------------------------------------- defrag
    def _maybe_defrag(self) -> bool:
        """Background compaction (docs/kv_layout.md): when fragmentation
        exceeds EngineConfig.kv_defrag_threshold, migrate the worst-
        fragmented resident sequence's movable block suffix into a free
        run — an on-device gather+scatter (block_copy.move_blocks)
        followed by pool.relocate, so hash registrations and refcounts
        follow the blocks and the old ids coalesce back into the
        free-run index. Constraints: only blocks owned by ONE sequence
        move (shared prefix-hit blocks stay put), targets come from the
        UNINIT free space only (never evicts cached prefixes), and the
        pass is skipped while a replay recorder is attached (the copy
        is a device program the follower/replay streams don't carry).
        Rate-limited to one pass per 64 decode steps. Called with at most
        one step in flight, which is harvested before anything moves."""
        cfg = self.cfg
        if (not cfg.kv_contig_alloc or cfg.kv_defrag_threshold <= 0
                or self.recorder is not None
                or self._step - self._defrag_last_step < 64):
            return False
        pool = self.kv_manager.pool
        thr = cfg.kv_defrag_threshold
        pool_frag = pool.frag_ratio()
        best = None   # (runs, seq_frag, slot, suffix_start, suffix)
        for i, req in enumerate(self.slots):
            if req is None or not req.ready or len(req.blocks) < 2:
                continue
            j = _own_suffix_start(pool, req.blocks)
            suffix = req.blocks[j:][:cfg.kv_defrag_max_blocks]
            if len(suffix) < 2:
                continue
            runs = pool.count_runs(suffix)
            if runs < 2:
                continue
            seq_frag = (runs - 1) / (len(suffix) - 1)
            if (pool_frag <= thr and seq_frag <= thr):
                continue
            if best is None or runs > best[0]:
                best = (runs, seq_frag, i, j, suffix)
        if best is None or pool.free_uninit_blocks < len(best[4]):
            return False
        runs, _seq_frag, slot, j, old = best
        if self._pending is not None:
            # nothing in flight while blocks move: harvest the one step
            req = self.slots[slot]
            self._drain("defrag")
            self.clock.enter("sweep")
            if self.slots[slot] is not req:
                return False        # it finished with that token
        new = pool.alloc_uninit(len(old))
        if new is None:
            return False
        if pool.count_runs(new) >= runs:
            pool.release(new)       # no layout win — don't thrash
            return False
        from .block_copy import move_blocks
        req = self.slots[slot]
        win_old, win_new = self._defrag_window_moves(req, len(old))
        self.kv = move_blocks(self.kv, old, new, cfg.kv_block_size,
                              win_src=win_old, win_dst=win_new)
        pool.relocate(zip(old, new))
        req.blocks[j:j + len(old)] = new
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :len(req.blocks)] = req.blocks
        if self.has_window_pool:
            self.kv_manager.win_pool.relocate(zip(win_old, win_new))
            moved = dict(zip(win_old, win_new))
            req.win.held = {i: moved.get(b, b)
                            for i, b in req.win.held.items()}
            self._window_ring(slot, req)
        self.defrag_passes += 1
        self._defrag_last_step = self._step
        self.flight.record("defrag", moved=len(old), runs_before=runs)
        logger.debug("defrag: slot %d moved %d blocks (%d runs → %d), "
                     "pool frag %.2f", slot, len(old), runs,
                     pool.count_runs(new), pool_frag)
        return True

    def _defrag_window_moves(self, req: "EngineRequest",
                             n: int) -> tuple:
        """The window-group half of a defrag pass that moves ``n`` paged
        blocks of ``req``: the window blocks it alone holds (at most as
        many, so that the copy program's shape is the paged move's), onto
        one fresh run of the window pool. ([], []) where there is no
        window pool, nothing to gain, or no free run."""
        if not self.has_window_pool:
            return [], []
        wp = self.kv_manager.win_pool
        held = [b for _i, b in sorted(req.win.held.items())]
        old = [b for b, rc in zip(held, wp.refcounts(held)) if rc == 1][-n:]
        if len(old) < 2 or wp.count_runs(old) < 2 \
                or wp.free_uninit_blocks < len(old):
            return [], []
        new = wp.alloc_uninit(len(old))
        if new is None or wp.count_runs(new) >= wp.count_runs(old):
            if new:
                wp.release(new)
            return [], []
        return old, new

    def _sweep_cancelled(self) -> bool:
        """One pass of the end-to-end cancellation contract
        (docs/chaos.md): cancelled/deadline-exceeded requests leave the
        waiting queue before ever taking a slot, and READY slots are
        vacated immediately — blocks released, offload write-back still
        honored via _release_slot. Slots with an un-harvested dispatch
        in flight are left to their harvest's own cancel check (same
        loop tick); non-ready slots (onboard in flight) resolve at
        _complete_onboards."""
        progressed = False
        if not self.waiting.empty():
            survivors: List[EngineRequest] = []
            while not self.waiting.empty():
                try:
                    r: EngineRequest = self.waiting.get_nowait()
                except asyncio.QueueEmpty:
                    break
                if r.cancelled:
                    self._finish_request(r, FinishReason.CANCELLED)
                    progressed = True
                else:
                    survivors.append(r)
            for r in survivors:
                self.waiting.put_nowait(r)
        if self._pending is None and self._ragged_pending is None:
            for req in list(self.slots):
                if req is not None and req.ready and req.cancelled:
                    self._release_slot(req)
                    self._finish_request(req, FinishReason.CANCELLED)
                    progressed = True
        return progressed

    # ---------------------------------------------------------------- admit
    def _try_admit(self, req: EngineRequest, slot: int) -> bool:
        plan = self.kv_manager.prepare_prefill(req.prompt, seq=req.seq,
                                               cold=req.cold_admission)
        if plan is None:
            return False
        if req.tenant:
            # per-tenant admission + prefix-hit accounting (the
            # nv_llm_tenant_* gauge feed; llm/tenancy.py)
            t = req.tenant
            self.tenant_admitted[t] = self.tenant_admitted.get(t, 0) + 1
            self.tenant_queries[t] = (self.tenant_queries.get(t, 0)
                                      + len(plan.all_blocks))
            self.tenant_hits[t] = (self.tenant_hits.get(t, 0)
                                   + len(plan.hit_blocks)
                                   + len(plan.host_slots)
                                   + len(plan.disk_hashes)
                                   + len(plan.remote_hashes))
        if len(plan.all_blocks) > self.M:
            # longer than a block table row — reject rather than overflow
            # the table (external prompts are length-checked upstream, but
            # preemption-grown prompts and misconfigured callers land here)
            self.kv_manager.abort_plan(plan)
            self._finish_request(req, FinishReason.LENGTH)
            return True
        if plan.host_slots or plan.disk_hashes or plan.remote_hashes:
            # host/disk/remote-tier hits: the wire→block-major copies
            # (and the disk file reads / fabric fetches) are pure host
            # work — run them OFF the loop (reference overlaps its tier
            # copies with compute via CopyStream, kv/layer.rs; our
            # analog is a thread + deferred admission) and finish
            # admitting when ready
            self._start_onboard(req, slot, plan)
            return True
        return self._admit_with_plan(req, slot, plan, None)

    def _emit_kv_store(self, items: list) -> None:
        """Offload-pump commit hook → the recorder stream. Multihost
        followers AND the offline replayer mirror the store (gathering
        the same device blocks from their own bit-identical KV), making
        host-tier restores replayable in both
        (replay.exec_kv_store_event). ``spills`` lists the evicted
        hashes this batch's host evictions handed to the disk spill
        queue (the enqueue-accept decision, made synchronously inside
        host_pool.store via _on_host_evict) — followers stage a copy of
        exactly those rows so the later "kv_disk_store" commit can apply
        the leader's literal placements from bit-identical bytes."""
        spills, self._pending_spills = self._pending_spills, []
        if self.recorder is not None:
            self.recorder.rec("kv_store", items=items, spills=spills)

    # ------------------------------------------------------- disk (G3) tier
    def _on_host_evict(self, seq_hash: int, tokens_hash, parent_hash,
                       values: dict) -> None:
        """Host-pool eviction hook (fires on the loop, inside the offload
        pump's store, with a fresh copy of the arena row): offer the
        block to the disk spill queue — async write-behind, never
        stalling the loop; saturation drops with a counter."""
        from ..llm.kv.diskstore import SpillJob
        accepted = self.spill_engine.offer(SpillJob(
            seq_hash=seq_hash, tokens_hash=tokens_hash,
            parent_hash=parent_hash, values=values))
        if accepted:
            self._pending_spills.append(seq_hash)

    def _emit_kv_disk_store(self, items: list) -> None:
        """Spill-pump commit hook: [(hash, tokens_hash, parent, evicted)]
        per durably-acknowledged disk put. Streams the literal placement
        decisions to multihost followers (replay.exec_kv_disk_store_event
        applies them from the staged row copies) and announces the
        spilled prefixes to the router's radix index with a "disk" tier
        tag — unless the hash is still device-registered (its device
        announce stands at full weight)."""
        if self.recorder is not None:
            self.recorder.rec("kv_disk_store", items=items)
        pub = self.kv_event_publisher
        if pub is None:
            return
        for h, th, ph, evicted in items:
            for gone in evicted:
                self._publish_tier_removed(gone)
            if not self.kv_manager.pool.peek_prefix([h]):
                pub.publish_stored(-1, h, th, ph, tier="disk")

    # ---------------------------------------------------- remote (G4) tier
    def _on_disk_evict(self, seq_hash: int, tokens_hash, parent_hash,
                       values: dict) -> None:
        """Disk-tier capacity-eviction hook: offer the block to the
        remote promotion pump (object-store write-behind) so a prefix
        leaving this worker's disk survives in the fleet. Fires on the
        spill pump's WORKER thread (inside DiskKvStore.put's eviction) —
        hop to the loop before touching the asyncio queue."""
        if self.remote_spill_engine is None or self._loop is None:
            return
        from ..llm.kv.diskstore import SpillJob
        job = SpillJob(seq_hash=seq_hash, tokens_hash=tokens_hash,
                       parent_hash=parent_hash, values=values)
        try:
            self._loop.call_soon_threadsafe(self._offer_remote_spill, job)
        except RuntimeError:
            pass                           # loop already closed (shutdown)

    def _offer_remote_spill(self, job) -> None:
        self.remote_spill_engine.offer(job)

    def _emit_kv_remote_store(self, items: list) -> None:
        """Remote promotion commit hook: [(hash, tokens_hash, parent,
        evicted)] per durably-acknowledged object put. Announces the
        promoted prefixes tier="remote" — unless a warmer tier still
        holds the hash (its announce stands at a better weight). The
        remote tier is NOT mirrored to multihost followers: the object
        store is fleet-shared state, not per-rank state, and followers
        never run the admission cascade."""
        pub = self.kv_event_publisher
        if pub is None:
            return
        host = self.kv_manager.host_pool
        for h, th, ph, evicted in items:
            for gone in evicted:
                self._publish_tier_removed(gone)
            if self.kv_manager.pool.peek_prefix([h]):
                continue
            if host is not None and host.contains(h):
                continue
            if self.disk_store is not None and self.disk_store.contains(h):
                continue
            pub.publish_stored(-1, h, th, ph, tier="remote")

    def enable_tenancy(self, ledger=None) -> None:
        """Attach a per-tenant block ledger (llm/tenancy.py
        TenantBlockLedger) and thread it through every present KV tier:
        device pool eviction prefers over-quota tenants' blocks, and
        the host/disk/remote stores account + quota-prefer likewise.
        Idempotent; untenanted engines never pay for any of it."""
        from ..llm.tenancy import TenantBlockLedger
        if ledger is None:
            ledger = self.tenancy or TenantBlockLedger()
        self.tenancy = ledger
        self.kv_manager.pool.tenancy = ledger
        self.kv_manager.tenancy = ledger
        host = self.kv_manager.host_pool
        if host is not None:
            host.tenancy = ledger
        if self.disk_store is not None:
            self.disk_store.tenancy = ledger
        if self.remote_store is not None:
            self.remote_store.tenancy = ledger

    def attach_kv_fabric(self, fabric) -> None:
        """Wire an attached fleet fabric (llm/kv/fabric.py KvFabric):
        its RemoteKvStore becomes the cascade's G4 rung. Engine-side
        construction (kv_remote_dir) may already have built an
        object-backed store — the fabric wraps that same store, so this
        is idempotent on the manager side."""
        if self.beside_paged_rows:
            raise NotImplementedError(
                "the KV fabric ships paged rows only; it is not "
                "implemented with " + " and ".join(self.beside_paged_rows))
        self.kv_fabric = fabric
        self.remote_store = fabric.store
        self.kv_manager.remote_store = fabric.store

    def kv_bytes_per_block(self) -> int:
        """Wire bytes one KV block moves (all layers/streams) — the
        admission gate's and the router's transfer-cost unit."""
        total = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                    for v in self.kv.values())
        return max(total // max(self.cfg.num_kv_blocks, 1), 1)

    def measured_prefill_tok_per_s(self) -> float:
        """MEASURED prefill rate — the recompute side of the fabric's
        fetch-vs-recompute model. AGE-WEIGHTED (llm/kv/fabric.
        PrefillRateEstimator): the first admissions — which include XLA
        compile on a young engine — are excluded, and later ones decay-
        average, so the gate prices recompute at the warmed-up rate.
        0.0 while young/unknown (the gate treats unknown as admit)."""
        return self.prefill_rate_estimator.rate()

    async def publish_prefix_to_remote(self, seq) -> int:
        """Prefill-as-a-Service publish (components/prefill_service.py):
        push every still-registered FULL block of ``seq``'s chain from
        the device pool to the durable remote (object) tier, keyed by
        the same chained hashes every other tier uses. Any decode fleet
        pointed at the same object root then admits the prefix through
        the existing cascade, priced by its own measured AdmissionGate
        crossover — no new decode path, no handoff stream.

        The device gather dispatches on the loop (ordered before any
        later donated KV update by the single device stream); the host
        fetch, npz pack, and object puts run off-thread (DL001: file
        I/O never rides the engine loop). Already-resident objects are
        skipped (content-addressed no-op). Returns blocks published."""
        rs = self.remote_store
        if rs is None or rs.object is None:
            return 0
        pool = self.kv_manager.pool
        # longest still-registered run of the chain, with refcount holds
        # so the blocks cannot be evicted under the gather (works on both
        # the Python and the native C++ pool)
        bids = pool.match_prefix(seq.sequence_hashes)
        if not bids:
            return 0
        entries = [(bids[j], seq.sequence_hashes[j], seq.block_hashes[j],
                    seq.sequence_hashes[j - 1] if j > 0 else None)
                   for j in range(len(bids))]
        try:
            from .block_copy import fetch_wire, gather_blocks_dispatch
            stacked = gather_blocks_dispatch(
                self.kv, [bid for bid, _h, _t, _p in entries],
                self.cfg.kv_block_size)

            def publish_all() -> int:
                from ..runtime.faults import hit as _fault
                values = fetch_wire(stacked, len(entries),
                                    self.wire_kv_heads)
                n = 0
                for i, (_bid, h, th, ph) in enumerate(entries):
                    if rs.object.contains(h):
                        continue           # content-addressed no-op
                    try:
                        _fault("prefill.publish")   # enospc/delay chaos
                        rs.put(h, {k: np.ascontiguousarray(v[:, :, i])
                                   for k, v in values.items()},
                               tokens_hash=th, parent_hash=ph)
                    except OSError as e:
                        # a refusing object tier (full bucket, chaos)
                        # forfeits THIS block's publish and keeps going:
                        # decode fleets simply recompute what never
                        # landed — publish is an optimization, not a
                        # correctness dependency
                        logger.warning("prefix publish of %x failed: %s",
                                       h & 0xFFFFFFFFFFFFFFFF, e)
                        continue
                    n += 1
                return n

            n = await asyncio.to_thread(publish_all)
        finally:
            pool.release(bids)
        self.prefill_published_blocks += n
        return n

    def _publish_tier_removed(self, seq_hash: int) -> None:
        """Removed-from-disk announce, suppressed while any warmer OR
        colder tier still holds the hash (the router would otherwise
        lose a prefix this worker can still serve). A disk eviction
        whose block was promoted to the durable remote tier DEMOTES the
        announce to tier="remote" instead."""
        pub = self.kv_event_publisher
        if pub is None:
            return
        host = self.kv_manager.host_pool
        if self.kv_manager.pool.peek_prefix([seq_hash]):
            return
        if host is not None and host.contains(seq_hash):
            return
        if (self.remote_store is not None
                and self.remote_store.holds_durable(seq_hash)):
            pub.publish_stored(-1, seq_hash, None, None, tier="remote")
            return
        pub.publish_removed([seq_hash])

    def _on_block_stored(self, bid: int, seq_hash: int, tokens_hash: int,
                         parent_hash) -> None:
        """Device-pool stored hook → tier-tagged router event (default
        tier "device")."""
        if self.kv_event_publisher is not None:
            self.kv_event_publisher.publish_stored(
                bid, seq_hash, tokens_hash, parent_hash)

    def _on_block_removed(self, seq_hashes: list) -> None:
        """Device-pool removed hook. A hash still resident in a colder
        tier is DEMOTED (re-announced with the tier tag) instead of
        removed — the router's radix index keeps the prefix visible at a
        discounted depth (kv_router/scoring.py TIER_WEIGHTS) rather than
        forgetting this worker can still serve it without recompute."""
        pub = self.kv_event_publisher
        if pub is None:
            return
        host = self.kv_manager.host_pool
        gone = []
        for h in seq_hashes:
            if host is not None and host.contains(h):
                th, ph = host.meta_for(h)
                pub.publish_stored(-1, h, th, ph, tier="host")
            elif self.disk_store is not None and self.disk_store.contains(h):
                pub.publish_stored(-1, h, None, None, tier="disk")
            elif (self.remote_store is not None
                  and self.remote_store.holds_durable(h)):
                pub.publish_stored(-1, h, None, None, tier="remote")
            else:
                gone.append(h)
        if gone:
            pub.publish_removed(gone)

    def _start_onboard(self, req: EngineRequest, slot: int, plan) -> None:
        """Reserve the slot, then prepare the host/disk-tier values
        off-thread; the loop's onboard step completes the admission (the
        decode batch keeps stepping during the copies). Disk hits promote
        through the SAME path — the tier-2 analog of the CopyStream
        overlap the host tier already implements; the matched disk
        entries were pinned at match time (prepare_prefill) and unpin in
        _complete_onboards."""
        req.slot = slot
        req.ready = False
        self.slots[slot] = req            # reserve (skipped by dispatch)
        self.host_onboards += 1
        if plan.disk_hashes:
            self.disk_onboards += 1
            self.disk_onboarded_blocks += len(plan.disk_hashes)
        if plan.remote_hashes:
            self.remote_onboards += 1
            self.remote_onboarded_blocks += len(plan.remote_hashes)
        host_pool = self.kv_manager.host_pool
        disk = self.disk_store
        remote = self.remote_store
        host_pool.pin(plan.host_slots)    # offload stores must not evict

        # trace identity travels BY VALUE into the prep thread (contextvars
        # don't cross to_thread): fabric RPCs forward it so the serving
        # peer's read lands in the same fleet tree
        trace_ctx = (req.trace.wire_context()
                     if req.trace is not None else None)

        # the recorder's kv_remote_restore event ships the FETCHED bytes
        # (the fleet-shared tier cannot be re-walked by a follower);
        # captured here only when a recorder is attached — otherwise the
        # bulk values are dropped as soon as they are scattered
        rec_remote: dict = {}

        async def prepare() -> None:
            prepped = None
            _t_prep0 = time.monotonic()
            fetch_ms = {"host": 0.0, "disk": 0.0, "remote": 0.0}
            try:
                def prep():
                    from ..runtime.faults import hit as _fault
                    from .block_copy import prep_host_values
                    _fault("engine.onboard")   # chaos: tier prep fails
                    parts = []
                    if plan.host_slots:
                        _t = time.monotonic()
                        parts.append(host_pool.fetch(plan.host_slots))
                        fetch_ms["host"] = 1e3 * (time.monotonic() - _t)
                    if plan.disk_hashes:
                        _t = time.monotonic()
                        parts.append(disk.fetch(plan.disk_hashes))
                        fetch_ms["disk"] = 1e3 * (time.monotonic() - _t)
                    if plan.remote_hashes:
                        # G4 fetch: peer RPC / object read. Unreachable
                        # (peer died, object torn) is NOT an error — drop
                        # the remote tail from the plan and the engine
                        # recomputes those tokens (graceful fallback:
                        # the fabric must never make serving worse than
                        # a cold prefill)
                        _t = time.monotonic()
                        try:
                            fetched = remote.fetch(plan.remote_hashes,
                                                   trace_ctx=trace_ctx)
                            parts.append(fetched)
                            if self.recorder is not None:
                                rec_remote["values"] = fetched
                        except Exception:  # noqa: BLE001
                            logger.warning(
                                "remote KV fetch of %d block(s) failed "
                                "for %s — recomputing the tail",
                                len(plan.remote_hashes), req.rid,
                                exc_info=True)
                            self.remote_fetch_failures += 1
                            self.remote_onboarded_blocks -= len(
                                plan.remote_hashes)
                            remote.unpin(plan.remote_hashes)
                            plan.remote_hashes = []
                        fetch_ms["remote"] = 1e3 * (time.monotonic() - _t)
                    if not parts:
                        # every tier hit fell away: admit with no onboard
                        return [], {}
                    n_onboard = (len(plan.host_slots)
                                 + len(plan.disk_hashes)
                                 + len(plan.remote_hashes))
                    targets = plan.new_blocks[:n_onboard]
                    vals = (parts[0] if len(parts) == 1 else
                            {k: np.concatenate([p[k] for p in parts],
                                               axis=2)
                             for k in parts[0]})
                    return prep_host_values(targets, vals)

                prepped = await asyncio.to_thread(prep)
            except asyncio.CancelledError:
                raise      # stop(): finally below records the dead onboard
            except Exception:  # noqa: BLE001
                logger.exception("host-tier onboard prep failed for %s",
                                 req.rid)
            finally:
                _t_prep1 = time.monotonic()
                self.flight.record(
                    "onboard", rid=req.rid,
                    host_blocks=len(plan.host_slots),
                    disk_blocks=len(plan.disk_hashes),
                    remote_blocks=len(plan.remote_hashes),
                    host_ms=round(fetch_ms["host"], 3),
                    disk_ms=round(fetch_ms["disk"], 3),
                    fabric_fetch_ms=round(fetch_ms["remote"], 3),
                    total_ms=round(1e3 * (_t_prep1 - _t_prep0), 3))
                if req.trace is not None:
                    req.trace.add_span(
                        "kv.onboard", _t_prep0, _t_prep1,
                        host_blocks=len(plan.host_slots),
                        disk_blocks=len(plan.disk_hashes),
                        remote_blocks=len(plan.remote_hashes),
                        fabric_fetch_ms=round(fetch_ms["remote"], 3))
                # pins release in _complete_onboards, AFTER the admission
                # records hit_transfer: an offload-pump eviction of these
                # slots must not be stream-ordered before the event, or a
                # multihost follower's mirror restore would read the
                # clobbered slot (the leader scatters prefetched values
                # and would not notice the divergence)
                self._onboards.append((req, slot, plan, prepped,
                                       rec_remote.get("values")))
                self._work_event.set()

        task = asyncio.get_running_loop().create_task(
            prepare(), name=f"kv-onboard-{req.rid}")
        self._onboard_tasks.add(task)
        task.add_done_callback(self._onboard_tasks.discard)

    def _complete_onboards(self) -> None:
        pending, self._onboards = self._onboards, []
        for req, slot, plan, prepped, remote_values in pending:
            self.slots[slot] = None       # _admit_with_plan re-reserves
            try:
                if req.cancelled or prepped is None:
                    self.kv_manager.pool.release(plan.all_blocks)
                    if req.cancelled:
                        self._finish_request(req, FinishReason.CANCELLED)
                    elif not req.cold_admission:
                        # tier onboard prep failed (dead disk, torn
                        # fetch, chaos injection): re-admit COLD — skip
                        # the offload cascade and recompute the prefix.
                        # A broken cache tier must degrade to a cold
                        # prefill, never to a failed request.
                        self.onboard_cold_retries += 1
                        req.cold_admission = True
                        req.slot = -1
                        req.ready = True
                        logger.warning(
                            "onboard prep failed for %s — retrying as a "
                            "cold admission (recompute)", req.rid)
                        self.waiting.put_nowait(req)
                        self._work_event.set()
                    else:
                        self._finish_request(req, FinishReason.ERROR)
                    continue
                with self.clock.phase("admit"):
                    self._admit_with_plan(req, slot, plan, prepped,
                                          remote_values=remote_values)
            finally:
                # _start_onboard pinned these; safe to evict only now
                # that hit_transfer (if any) is on the stream. A failed
                # remote fetch already unpinned and cleared remote_hashes
                # inside the prep (graceful fallback).
                self.kv_manager.host_pool.unpin(plan.host_slots)
                if plan.disk_hashes:
                    self.disk_store.unpin(plan.disk_hashes)
                if plan.remote_hashes:
                    self.remote_store.unpin(plan.remote_hashes)

    def _admit_with_plan(self, req: EngineRequest, slot: int, plan,
                         onboard, remote_values=None) -> bool:
        n_prompt = len(req.prompt)
        _t_admit = req.admitted_time = time.monotonic()
        req.prefill_chunks = 0
        if req.trace is not None:
            # queue-wait phase on the request's fleet trace: enqueue →
            # the moment a slot + KV plan existed for it
            req.trace.add_span("engine.queue_wait", req.enqueue_time,
                               _t_admit)
        req.slot = slot
        req.blocks = plan.all_blocks
        req.seq = plan.seq
        req.win = plan.win
        # host-tier hits: scatter the prepared (block-major, padded) values
        # into their device slots before prefill (reference
        # prepare_prefill_offload; the +40% TTFT multi-turn win,
        # docs/architecture.md:91)
        n_onboard = (len(plan.host_slots) + len(plan.disk_hashes)
                     + len(plan.remote_hashes))
        if n_onboard:
            from .block_copy import scatter_prepped
            ids, vals = onboard
            self.kv = scatter_prepped(self.kv, ids, vals,
                                      self.cfg.kv_block_size)
            targets = plan.new_blocks[:n_onboard]
            # onboarded blocks now hold valid registered content
            n_dev = len(plan.hit_blocks)
            for i, bid in enumerate(targets):
                j = n_dev + i
                parent = plan.seq.sequence_hashes[j - 1] if j > 0 else None
                self.kv_manager.pool.register(
                    bid, plan.seq.sequence_hashes[j],
                    plan.seq.block_hashes[j], parent)
        req.prefix_hit_tokens = (plan.hit_tokens + plan.host_hit_tokens
                                 + plan.disk_hit_tokens
                                 + plan.remote_hit_tokens)
        n_already = len(plan.hit_blocks) + n_onboard
        if self.recorder is not None and req.prefix_hit_tokens > 0:
            # before the prefill record: read rights over the shared
            # prefix. host_hit + host_slots/targets let multihost
            # followers and the offline replayer re-execute the h2d
            # restore above from their mirror pools
            # (replay.exec_host_restore_event); disk_hashes/disk_targets
            # do the same for the G3 promote (the follower fetches the
            # hashes from its own mirror disk store)
            n_host = len(plan.host_slots)
            n_hd = n_host + len(plan.disk_hashes)
            if plan.remote_hashes:
                # fleet-shared (G4) tier: followers never run the
                # admission cascade, so a remote-assisted admission
                # streams as its OWN event carrying the fetched hashes
                # AND the fetched bytes — recorded BEFORE hit_transfer
                # so the replayed restore marks the remote targets
                # written before the hit walk reads them. Followers and
                # the offline replayer scatter the literal bytes
                # (replay.exec_kv_remote_restore_event); a follower
                # whose OWN remote store holds the hashes may fetch
                # them instead (fetch-or-bytes — the object tier is
                # content-addressed, so the bytes are identical by
                # construction). This retired the round-6 refusal.
                if remote_values is None:
                    raise RuntimeError(
                        "recorded remote onboarding without captured "
                        "fetch values — prep/recorder wiring drifted")
                self.recorder.rec(
                    "kv_remote_restore", rid=req.rid,
                    remote_hashes=list(plan.remote_hashes),
                    remote_targets=list(
                        plan.new_blocks[n_hd:n_hd
                                        + len(plan.remote_hashes)]),
                    values={k: np.asarray(v)
                            for k, v in remote_values.items()})
            self.recorder.rec("hit_transfer", rid=req.rid,
                              hit=req.prefix_hit_tokens,
                              host_hit=plan.host_hit_tokens,
                              disk_hit=plan.disk_hit_tokens,
                              blocks=list(plan.all_blocks),
                              # multihost followers replay the h2d restore
                              # from their mirror pool at these slots into
                              # these device blocks (run_follower)
                              host_slots=list(plan.host_slots),
                              host_targets=list(
                                  plan.new_blocks[:n_host]),
                              disk_hashes=list(plan.disk_hashes),
                              disk_targets=list(
                                  plan.new_blocks[n_host:n_hd]))
        t0 = time.monotonic()
        wait0 = self.clock.seconds["wait"]
        suffix_len = n_prompt - req.prefix_hit_tokens
        if (self._ragged_jit is not None and req.handoff is None
                and req.precomputed is None and suffix_len > 0):
            # ragged serving: EVERY normal admission rides the ragged
            # batch as a prefill lane — no dedicated prefill dispatch,
            # continuous batching is the only code path. Disagg
            # handoff/precomputed admissions keep the prefill program
            # (their gather/scatter contracts are prefill-shaped).
            self._admit_lane(req, slot, n_already)
            return True
        if (self.cfg.lane_prefill_max_tokens > 0
                and self._decode_k_jit is not None
                and req.handoff is None and req.precomputed is None
                and 0 < suffix_len <= self.cfg.lane_prefill_max_tokens
                and any(s is not None and s.ready for s in self.slots)):
            # lane prefill: the engine is already decoding — ride the
            # decode batch instead of stalling it with a prefill dispatch
            self._admit_lane(req, slot, n_already)
            return True
        defer = False
        # prompt rows whose expert layers run grouped (the prefill
        # record's grouped_rows): the model's own chooser, asked per
        # dispatched program shape
        grouped_rows = 0
        # the module's prefill_counters (none of precomputed rows)
        counters = {}
        remote_admit = req.precomputed is not None
        if remote_admit:
            from ..llm.kv.stream import LayerStreamPayload
            if (isinstance(req.precomputed, LayerStreamPayload)
                    and not req.precomputed.complete):
                # streaming layer-wise handoff: admit NOW (slot reserved,
                # decode-invisible) and scatter layers as frames land —
                # the request becomes decode-ready the tick the last
                # layer arrives (llm/kv/stream.py; _stream_onboard)
                return self._admit_stream(req, slot, plan, n_already,
                                          _t_admit)
        if req.precomputed is not None:
            tok, logprob = self._admit_precomputed(req, n_already)
            # device payloads ship the first token as a device scalar (the
            # prefill side never fetched it — one round-trip saved); defer
            # our fetch behind the next decode dispatch like a local
            # admission
            defer = hasattr(tok, "copy_to_host_async")
            fetch = not defer
            t_dispatched = time.monotonic()
        else:
            # prefill only the un-matched suffix — the prefix KV is already
            # in the pool's blocks (this is the TTFT win of prefix reuse)
            chunk = req.prompt[req.prefix_hit_tokens:]
            bucket = self.cfg.bucket_for(len(chunk))
            table = self._prefill_table(req.blocks, slot)
            req.prefill_chunks = 1      # _chunked_prefill counts its own
            key = make_slot_keys(self.cfg.seed,
                                 jnp.asarray([req.sampling.seed]),
                                 jnp.asarray(req.key_step))[0]
            use_sp = (self._prefill_sp_jit is not None
                      and req.prefix_hit_tokens == 0
                      and len(chunk) >= self.cfg.sp_min_prefill_tokens
                      and bucket % self._sp == 0
                      # ring attention supports neither score soft-capping
                      # nor sliding-window layers (gemma2)
                      and self.model_cfg.attn_logit_softcap is None
                      and self.model_cfg.sliding_window is None)
            if use_sp:
                padded = np.zeros((bucket,), np.int32)
                padded[:len(chunk)] = chunk
                if self.recorder is not None:
                    # streamable like plain prefill (start_pos is always 0
                    # on the sp path) — multihost followers replay it
                    req._pf_seq = self.recorder.next_dispatch_id()
                    self.recorder.rec(
                        "prefill_sp", pf_seq=req._pf_seq, rid=req.rid,
                        slot=slot, padded=padded.copy(), table=table.copy(),
                        true_len=len(chunk), samp_seed=req.sampling.seed,
                        key_step=req.key_step,
                        temp=req.sampling.temperature,
                        top_k=req.sampling.top_k, top_p=req.sampling.top_p)
                tok, logprob, self.kv = self._prefill_sp_jit(
                    self.params, self.kv, jnp.asarray(padded),
                    jnp.asarray(table), jnp.asarray(len(chunk), jnp.int32),
                    key,
                    jnp.asarray(req.sampling.temperature, jnp.float32),
                    jnp.asarray(req.sampling.top_k, jnp.int32),
                    jnp.asarray(req.sampling.top_p, jnp.float32))
            elif (self.cfg.prefill_chunk > 0
                    and len(chunk) > self.cfg.prefill_chunk):
                tok, logprob = self._chunked_prefill(req, chunk, table, key,
                                                     slot=slot)
                # the shape that was dispatched
                bucket = self.cfg.prefill_chunk
                grouped_rows = llama.grouped_prefill_rows(
                    self.statics, bucket, len(chunk))
            else:
                padded = np.zeros((bucket,), np.int32)
                padded[:len(chunk)] = chunk
                table = self._window_before(
                    req, table, req.prefix_hit_tokens, n_prompt)
                if self.recorder is not None:
                    req._pf_seq = self._rec_prefill(
                        req, slot, padded, table,
                        start_pos=req.prefix_hit_tokens,
                        true_len=len(chunk))
                tok, logprob, self.kv, *draft = self._prefill_jit(
                    self.params, self.kv, jnp.asarray(padded),
                    jnp.asarray(table),
                    jnp.asarray(req.prefix_hit_tokens, jnp.int32),
                    jnp.asarray(len(chunk), jnp.int32),
                    key,
                    jnp.asarray(req.sampling.temperature, jnp.float32),
                    jnp.asarray(req.sampling.top_k, jnp.int32),
                    jnp.asarray(req.sampling.top_p, jnp.float32),
                    *self._next_tok(-1))
                req.draft = draft[0] if draft else -1
                grouped_rows = llama.grouped_prefill_rows(
                    self.statics, bucket, len(chunk))
                self._window_after(req, n_prompt)
            counters = self.model_mod.prefill_counters(
                self.model_cfg, bucket, len(chunk), n_prompt)
            self.total_prefill_tokens += len(chunk)
            self.clock.admits += 1
            self.clock.admit_tokens += len(chunk)
            # measured prefill rate (fabric admission gate + the
            # router's NetKV recompute model): wall time from plan to
            # dispatched prefill — an upper bound on the true compute
            # cost, so the modeled recompute stays conservative
            t_dispatched = time.monotonic()
            self.prefill_rate_estimator.observe(len(chunk),
                                                t_dispatched - t0)
            # defer the device→host fetch of the first token: it overlaps
            # the next decode dispatch instead of stalling the loop. Wire
            # handoff needs the host value immediately; DEVICE handoff
            # never needs it at all — the token rides the payload as a
            # device scalar and the decode side defers its own fetch.
            defer = req.handoff is None
            fetch = not defer and not req.handoff_device
        if fetch:
            with self.clock.phase("wait"):
                tok, logprob = int(tok), float(logprob)
        req.pos = n_prompt
        req.generated = 1
        req.key_step += 1
        # the prompt's full blocks now hold valid KV — register for reuse
        req.registered_blocks = self.kv_manager.register_full_blocks(
            req.blocks, plan.seq, already_registered=n_already,
            tenant=req.tenant or None)
        if self.recorder is not None:
            self.recorder.rec(
                "admit", rid=req.rid, slot=slot, pos=req.pos,
                key_step=req.key_step, blocks=list(req.blocks),
                hit=req.prefix_hit_tokens, prompt=list(req.prompt))
        if req.handoff is not None:
            self._handoff_and_finish(req, tok, logprob)
            return True
        # engine.first_token (the request's trace) runs from here, the
        # prefill dispatch's return, to the first emit
        req.dispatched_time = t_dispatched
        if not defer:
            req.last_token = int(tok)
            req.draft = int(np.asarray(req.draft))
            self._mark_first_token(req)
            if self.recorder is not None:
                self.recorder.rec("first_token", rid=req.rid,
                                  pf_seq=getattr(req, "_pf_seq", None),
                                  tok=req.last_token)
        else:
            req.ready = False
            req.last_token = -1
            for a in (tok, logprob, req.draft):
                if hasattr(a, "copy_to_host_async"):
                    a.copy_to_host_async()
            self._admissions.append((req, tok, logprob))
        self.slots[slot] = req
        # host mirrors
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :len(req.blocks)] = req.blocks
        if self.has_window_pool:
            self._window_ring(slot, req)
        self._samp["temperature"][slot] = req.sampling.temperature
        self._samp["top_k"][slot] = req.sampling.top_k
        self._samp["top_p"][slot] = req.sampling.top_p
        self._seeds[slot] = req.sampling.seed
        logger.debug(
            "admitted %s into slot %d (prompt=%d, hit=%d+%dhost+%ddisk+"
            "%dremote, handoff=%s, %.1fms)", req.rid, slot, n_prompt,
            plan.hit_tokens, plan.host_hit_tokens, plan.disk_hit_tokens,
            plan.remote_hit_tokens, remote_admit,
            1e3 * (time.monotonic() - t0))
        now = time.monotonic()
        self.flight.record(
            "prefill", rid=req.rid, prompt=n_prompt,
            planned_tokens=suffix_len, batch_fill=sum(
                1 for s in self.slots if s is not None),
            hit_device=plan.hit_tokens, hit_host=plan.host_hit_tokens,
            hit_disk=plan.disk_hit_tokens,
            hit_remote=plan.remote_hit_tokens,
            # the hit as taken, and what of a longer paged match was given
            # up because the window blocks before its boundary were gone
            # (0 without a window pool)
            hit_tokens=req.prefix_hit_tokens,
            hit_cut_tokens=plan.hit_cut_tokens,
            precomputed=remote_admit, grouped_rows=grouped_rows,
            # the family's own keys (scan_tokens / key_tokens: 0 elsewhere;
            # dsa_blocks, dsa_blocks_run: a model with an indexer only)
            **{"scan_tokens": 0, "key_tokens": 0, **counters},
            host_ms=round(1e3 * (now - t0), 3),
            # of host_ms: plan to the prefill program's return (argument
            # build and transfers included), and the blocking fetch of
            # its token (0 when deferred behind the next decode dispatch)
            dispatch_ms=round(1e3 * (t_dispatched - t0), 3),
            wait_ms=round(1e3 * (self.clock.seconds["wait"] - wait0), 3),
            queue_wait_ms=round(1e3 * (_t_admit - req.enqueue_time), 3))
        if req.trace is not None:
            req.trace.add_span(
                "engine.prefill", t0, now, suffix=suffix_len,
                hit=req.prefix_hit_tokens,
                tiers={"device": plan.hit_tokens,
                       "host": plan.host_hit_tokens,
                       "disk": plan.disk_hit_tokens,
                       "remote": plan.remote_hit_tokens})
        if req.ready:
            self._emit(req, tok, float(logprob))
            self._maybe_finish_after_emit(req)
        return True

    def _admit_lane(self, req: EngineRequest, slot: int,
                    n_already: int) -> None:
        """Continuous-batching admission: no prefill dispatch — the prompt
        rides the decode batch as planned tokens (see EngineConfig.
        lane_prefill_max_tokens). Blocks are allocated (done by the caller's
        plan) but NOT registered yet: their KV is written step by step, so
        registration follows harvest progress exactly like decode."""
        self.lane_admissions += 1
        n_prompt = len(req.prompt)
        hit = req.prefix_hit_tokens
        # the first generated token comes from the decode program here
        # (an uncontended run derives it via the prefill program) — a
        # numeric boundary for the exactness contract
        req.numeric_boundaries.append(req.emitted_total)
        req.lane_prompt = list(req.prompt)
        req.pos = hit
        req.generated = 0
        # sampling-key parity with the prefill path: the step consuming the
        # last prompt token samples the first generation and must use the
        # request's CURRENT key_step; planned steps before it burn earlier
        # (negative-offset) key values whose samples are discarded anyway
        req.key_step -= n_prompt - hit - 1
        req.last_token = req.prompt[hit]       # step-0 planned input
        req.ready = True
        req.dispatched_time = time.monotonic()   # no prefill to wait for
        # hash chain restarts from the hit prefix and grows per input token
        req.seq = TokenBlockSequence(self.cfg.kv_block_size,
                                     req.prompt[:hit])
        req.registered_blocks = n_already
        self.slots[slot] = req
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :len(req.blocks)] = req.blocks
        self._samp["temperature"][slot] = req.sampling.temperature
        self._samp["top_k"][slot] = req.sampling.top_k
        self._samp["top_p"][slot] = req.sampling.top_p
        self._seeds[slot] = req.sampling.seed
        if self.recorder is not None:
            self.recorder.rec(
                "admit", rid=req.rid, slot=slot, pos=req.pos,
                key_step=req.key_step, blocks=list(req.blocks),
                hit=hit, prompt=list(req.prompt), lane=True)
        logger.debug("lane-admitted %s into slot %d (prompt=%d, hit=%d)",
                     req.rid, slot, n_prompt, hit)

    def _prefill_table(self, blocks: list, slot: int) -> np.ndarray:
        """The block table a prefill dispatch takes: M entries, and for a
        model with per-slot state the slot behind them; for one
        with a window pool M more, the window block of every logical block
        (filled in before each dispatch: _window_before)."""
        table = np.zeros((self.M * (1 + int(self.has_window_pool))
                          + int(self.is_hybrid),), np.int32)
        table[:len(blocks)] = blocks
        if self.is_hybrid:
            table[self.M] = slot
        return table

    # ------------------------------------------------- the window group
    def _window_before(self, req: "EngineRequest", table: np.ndarray,
                       lo: int, hi: int) -> np.ndarray:
        """Before a prefill dispatch over the positions [lo, hi): take the
        window blocks its rows go to. → the dispatch's table, its window
        half filled in (the blocks its first query's window reaches are
        still held: a hit's, or the chunk's before). A table of its OWN:
        the dispatch before it may still be queued on the one it was given
        (jnp.asarray of an ndarray need not copy it)."""
        if not self.has_window_pool:
            return table
        bs = self.cfg.kv_block_size
        if not self.kv_manager.window_grow(req.win, lo // bs,
                                           -(-hi // bs)):
            # sized so that this cannot be (window_pool_blocks)
            raise RuntimeError("the window pool has no block left for a "
                               "prefill dispatch")
        table = table.copy()
        table[self.M:2 * self.M] = req.win.table(self.M)
        return table

    def _window_after(self, req: "EngineRequest", position: int) -> None:
        """After the rows before ``position`` were dispatched: register
        the window blocks that are full, and let go of those the query at
        ``position`` no longer reaches."""
        if not self.has_window_pool:
            return
        if req.seq is not None:
            self.kv_manager.window_register(req.win, req.seq, req.blocks,
                                            position)
        self.kv_manager.window_slide(req.win, position)

    def _window_ring(self, slot: int, req: "EngineRequest") -> None:
        """The slot's decode table, window part: logical block b at entry
        b % R."""
        ring = self._block_tables[slot, self.M:]
        ring[:] = 0
        for i, bid in req.win.held.items():
            ring[i % self.R] = bid

    def _next_tok(self, tok: int) -> tuple:
        """The prefill program's last argument under a resident drafter
        (the token after the chunk; -1: the one it samples); () without."""
        return (jnp.asarray(tok, jnp.int32),) if self.resident_drafter else ()

    def _rec_prefill(self, req: "EngineRequest", slot: int,
                     padded: np.ndarray, table: np.ndarray, *,
                     start_pos: int, true_len: int,
                     next_tok: int = -1) -> int:
        """Record one plain-prefill event (the ONE home of its field set —
        whole-prompt admissions and each chunk of a chunked admission both
        go through here). Returns the event's pf_seq."""
        pf = self.recorder.next_dispatch_id()
        self.recorder.rec(
            "prefill", pf_seq=pf, rid=req.rid, slot=slot,
            padded=padded.copy(), table=table.copy(),
            start_pos=start_pos, true_len=true_len,
            samp_seed=req.sampling.seed, key_step=req.key_step,
            temp=req.sampling.temperature,
            top_k=req.sampling.top_k, top_p=req.sampling.top_p,
            **({"next_tok": next_tok} if self.resident_drafter else {}))
        return pf

    def _chunked_prefill(self, req: EngineRequest, chunk: list,
                         table: np.ndarray, key, *, slot: int) -> tuple:
        """Prompt prefill as a sequence of fixed-size chunk dispatches
        (EngineConfig.prefill_chunk): each chunk continues at
        ``start_pos`` against the KV already written — the same mechanism
        as prefix-reuse continuation — so one compiled chunk shape serves
        any prompt length, bounding both compile count and per-dispatch
        activation memory (SURVEY.md §7 "blockwise prefill chunks"). Only
        the final chunk's sampled token matters. Each chunk records as a
        plain "prefill" event (it IS one), so chunked runs replay and
        stream to multihost followers."""
        C = self.cfg.prefill_chunk
        off = req.prefix_hit_tokens
        tok = logprob = None
        req.prefill_chunks = -(-len(chunk) // C)
        for lo in range(0, len(chunk), C):
            piece = chunk[lo:lo + C]
            # the tail pads to C too: exactly ONE compiled prefill shape
            # regardless of prompt length or bucket list
            padded = np.zeros((C,), np.int32)
            padded[:len(piece)] = piece
            table = self._window_before(req, table, off, off + len(piece))
            # a resident drafter's tail takes the token AFTER the chunk:
            # the prompt's next one, after the last chunk the sampled one
            nxt = chunk[lo + C] if lo + C < len(chunk) else -1
            if self.recorder is not None:
                pf = self._rec_prefill(req, slot, padded, table,
                                       start_pos=off, true_len=len(piece),
                                       next_tok=nxt)
                if lo + C >= len(chunk):
                    req._pf_seq = pf      # final chunk samples the token
            tok, logprob, self.kv, *draft = self._prefill_jit(
                self.params, self.kv, jnp.asarray(padded),
                jnp.asarray(table),
                jnp.asarray(off, jnp.int32),
                jnp.asarray(len(piece), jnp.int32),
                key,
                jnp.asarray(req.sampling.temperature, jnp.float32),
                jnp.asarray(req.sampling.top_k, jnp.int32),
                jnp.asarray(req.sampling.top_p, jnp.float32),
                *self._next_tok(nxt))
            req.draft = draft[0] if draft else -1
            off += len(piece)
            # the device runs programs in dispatch order: a window block
            # let go here is rewritten only by a later dispatch
            self._window_after(req, off)
        return tok, logprob

    def _complete_admissions(self) -> None:
        """Finish deferred admissions: the async device→host copies have
        been in flight across a decode dispatch; fetch, emit the first
        token, and make the slot decodable."""
        pending, self._admissions = self._admissions, []
        for req, tok_dev, logprob_dev in pending:
            # the async copies were issued at admission and usually land
            # during the intervening dispatch harvest — the wait phase
            # records what the fetches ACTUALLY cost (often ~0)
            with self.clock.phase("wait"):
                tok = int(np.asarray(tok_dev))
                logprob = float(np.asarray(logprob_dev))
                req.draft = int(np.asarray(req.draft))
            req.last_token = tok
            self._mark_first_token(req)
            req.ready = True
            if self.recorder is not None:
                self.recorder.rec("first_token", rid=req.rid,
                                  pf_seq=getattr(req, "_pf_seq", None),
                                  tok=tok)
            if self.slots[req.slot] is not req:
                continue               # raced away (shutdown edge)
            self._emit(req, tok, logprob)
            self._maybe_finish_after_emit(req)

    def _admit_precomputed(self, req: EngineRequest,
                           n_already: int) -> tuple:
        """Admission from a remote-prefill KV payload: scatter the shipped
        block values into this engine's paged pool instead of running the
        prefill program (the decode half of PD disaggregation; reference
        examples/llm/components/worker.py remote-prefill path). Blocks the
        decode engine already had (device/host prefix hits) are skipped —
        only the remainder is written."""
        pc = req.precomputed
        n_prompt_blocks = self._blocks_needed(len(req.prompt))
        targets = req.blocks[n_already:n_prompt_blocks]
        from ..llm.kv_transport import (DeviceKvPayload,
                                        scatter_blocks_device)
        if isinstance(pc, DeviceKvPayload) and self.recorder is not None:
            # device payloads are NOT copied onto the stream — their
            # arrays are device-resident. Each follower rank's co-located
            # prefill-engine replica parked its own shard of this payload
            # under the request id ("handoff_gather" park=True); stream
            # only the admission metadata and let each rank scatter its
            # local deposit (multihost.run_follower
            # "precomputed_device_admit"). Streamed even with empty
            # targets (full prefix hit): the followers must still CLAIM
            # and drop their parked shard or it would pin HBM forever.
            self.recorder.rec(
                "precomputed_device_admit", rid=req.rid,
                targets=list(targets), skip=n_already,
                n_needed=n_prompt_blocks)
        if targets:
            # (payload layout was validated at submit() — a raise here
            # would kill the engine loop)
            if isinstance(pc, DeviceKvPayload):
                # device bulk plane: blocks hop prefill-devices →
                # decode-devices (ICI, resharding under our mesh) with no
                # host staging
                self.kv = scatter_blocks_device(
                    self.kv, targets, pc, n_already, n_prompt_blocks,
                    mesh=self.mesh)
            else:
                vals = {k: v[:, :, n_already:n_prompt_blocks]
                        for k, v in pc.values.items()}
                if self.recorder is not None:
                    # wire-plane payload: stream the (global-head) values
                    # so multihost followers and the offline replayer can
                    # apply the identical scatter — recorded BEFORE the
                    # device op, like every streamed program
                    self.recorder.rec(
                        "precomputed_admit", rid=req.rid,
                        targets=list(targets),
                        values={k: np.asarray(v) for k, v in vals.items()})
                self.kv = scatter_blocks_from_host(
                    self.kv, targets, vals, self.cfg.kv_block_size)
        # drop the payload now: nothing reads it after the scatter, and a
        # DeviceKvPayload would otherwise pin the whole gathered KV stack
        # in the PREFILL engine's HBM for this request's lifetime
        req.precomputed = None
        return pc.first_token, pc.first_logprob

    def _admit_stream(self, req: EngineRequest, slot: int, plan,
                      n_already: int, t_admit: float) -> bool:
        """Admission against a still-arriving LayerStreamPayload
        (llm/kv/stream.py): the slot is reserved with the admission-time
        bookkeeping of a precomputed admit (pos/key_step/mirrors — so the
        later decode stream is bit-identical to the monolithic handoff),
        but the request stays ``ready=False`` — dispatches aim it at the
        trash block — while _stream_onboard scatters layers as they land.
        First-token emit, block registration, and the ``first_token``
        record all defer to stream completion; a dead stream re-admits
        COLD (the same graceful rung as a failed tier onboard)."""
        n_prompt = len(req.prompt)
        n_prompt_blocks = self._blocks_needed(n_prompt)
        req.pos = n_prompt
        req.generated = 1
        req.key_step += 1
        req.ready = False
        req.last_token = -1
        req.dispatched_time = time.monotonic()   # no prefill to wait for
        self.disagg_stream_admits += 1
        if self.recorder is not None:
            self.recorder.rec(
                "admit", rid=req.rid, slot=slot, pos=req.pos,
                key_step=req.key_step, blocks=list(req.blocks),
                hit=req.prefix_hit_tokens, prompt=list(req.prompt))
        self.slots[slot] = req
        self._block_tables[slot, :] = 0
        self._block_tables[slot, :len(req.blocks)] = req.blocks
        self._samp["temperature"][slot] = req.sampling.temperature
        self._samp["top_k"][slot] = req.sampling.top_k
        self._samp["top_p"][slot] = req.sampling.top_p
        self._seeds[slot] = req.sampling.seed
        logger.debug(
            "stream-admitted %s into slot %d (prompt=%d, hit=%d, "
            "%d layers inbound)", req.rid, slot, n_prompt,
            req.prefix_hit_tokens, req.precomputed.num_layers)
        self.flight.record(
            "prefill", rid=req.rid, prompt=n_prompt,
            planned_tokens=0, batch_fill=sum(
                1 for s in self.slots if s is not None),
            hit_device=plan.hit_tokens, hit_host=plan.host_hit_tokens,
            hit_disk=plan.disk_hit_tokens,
            hit_remote=plan.remote_hit_tokens,
            precomputed=True, grouped_rows=0,
            queue_wait_ms=round(1e3 * (t_admit - req.enqueue_time), 3))
        task = asyncio.get_running_loop().create_task(
            self._stream_onboard(req, plan, n_already, n_prompt_blocks),
            name=f"kv-stream-onboard-{req.rid}")
        self._stream_tasks.add(task)
        task.add_done_callback(self._stream_tasks.discard)
        return True

    async def _stream_onboard(self, req: EngineRequest, plan,
                              n_already: int,
                              n_prompt_blocks: int) -> None:
        """Progressive onboard of a layer stream: per layer, await the
        frame, prep OFF-thread (the existing tier-onboard discipline —
        the wire→block-major transpose never stalls the loop), then
        record ``kv_layer_stream`` and dispatch the scatter ADJACENTLY
        (no await between them, so recorder order equals device
        submission order — the bit-exact replay/follower contract)."""
        from .block_copy import (prep_layer_values, scatter_layer_prepped,
                                 slice_local_lanes)
        pc = req.precomputed
        t_wait = t_busy = 0.0
        try:
            for layer in range(pc.num_layers):
                _t0 = time.monotonic()
                vals = await pc.wait_layer(layer)
                _t1 = time.monotonic()
                t_wait += _t1 - _t0
                if req.cancelled or self.slots[req.slot] is not req:
                    return      # swept/raced away; blocks already handled
                # defrag may relocate this request's blocks between
                # layers (it copies content, so earlier layers move with
                # them) — re-read the live suffix targets each layer
                targets = req.blocks[n_already:n_prompt_blocks]
                if targets:
                    sliced = slice_local_lanes(
                        self.kv,
                        {k: v[:, n_already:n_prompt_blocks]
                         for k, v in vals.items()})
                    ids, prepped = await asyncio.to_thread(
                        prep_layer_values, targets, sliced)
                    if (req.cancelled
                            or self.slots[req.slot] is not req):
                        return
                    if self.recorder is not None:
                        self.recorder.rec(
                            "kv_layer_stream", rid=req.rid, layer=layer,
                            num_layers=pc.num_layers,
                            targets=list(targets),
                            values={k: np.asarray(v)
                                    for k, v in sliced.items()})
                    self.kv = scatter_layer_prepped(
                        self.kv, layer, ids, prepped,
                        self.cfg.kv_block_size)
                self.disagg_stream_layers_scattered += 1
                t_busy += time.monotonic() - _t1
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — dead stream → cold rung
            self.disagg_stream_fallbacks += 1
            self.disagg_stream_hidden_s += t_busy
            self.disagg_stream_exposed_s += t_wait
            if self.slots[req.slot] is not req:
                return
            logger.warning(
                "kv layer stream failed for %s (%s) — re-admitting as a "
                "cold recompute", req.rid, e)
            self._release_slot(req)
            if req.cancelled:
                self._finish_request(req, FinishReason.CANCELLED)
                return
            # restore pre-admission sampling state so the local
            # recompute samples exactly what an uncontended run would
            # (no key was consumed: the first token was the producer's)
            req.key_step -= 1
            req.pos = 0
            req.generated = 0
            req.precomputed = None
            req.seq = None
            req.slot = -1
            req.registered_blocks = 0
            req.prefix_hit_tokens = 0
            req.ready = True
            req.cold_admission = True
            self.waiting.put_nowait(req)
            self._work_event.set()
            return
        # completion: the pool now holds the full prompt KV — register,
        # surface the producer's first token, join the decode batch
        self.disagg_stream_hidden_s += t_busy
        self.disagg_stream_exposed_s += t_wait
        if pc.fallback_monolithic:
            self.disagg_stream_fallbacks += 1
        req.precomputed = None
        if req.cancelled or self.slots[req.slot] is not req:
            return
        req.registered_blocks = self.kv_manager.register_full_blocks(
            req.blocks, plan.seq, already_registered=n_already,
            tenant=req.tenant or None)
        tok, logprob = int(pc.first_token), float(pc.first_logprob)
        req.last_token = tok
        self._mark_first_token(req)
        req.ready = True
        if self.recorder is not None:
            self.recorder.rec("first_token", rid=req.rid, pf_seq=None,
                              tok=tok)
        self._emit(req, tok, logprob)
        self._maybe_finish_after_emit(req)
        self._work_event.set()

    def _handoff_and_finish(self, req: EngineRequest, tok: int,
                            logprob: float) -> None:
        """Prefill-worker epilogue: dispatch an on-device gather of the
        prompt's blocks (ordered before any later donated decode step by
        the device's program order), then ship device→DRAM→TCP off-thread
        so the engine loop keeps stepping during the DMA + DCN transfer."""
        from .block_copy import fetch_wire, gather_blocks_dispatch
        n_blocks = self._blocks_needed(req.pos)
        ids = req.blocks[:n_blocks]
        if self.recorder is not None:
            # a multihost PREFILL engine must stream the gather — it is a
            # device program, and an unstreamed dispatch would deadlock
            # followers at the next collective. park=True additionally
            # tells each follower rank to hold its shard of the gather
            # output in the process bridge so a co-located multihost
            # DECODE engine's follower can claim it on the leader's
            # "precomputed_device_admit" (multihost.run_follower)
            self.recorder.rec("handoff_gather", rid=req.rid,
                              ids=list(ids), n_blocks=n_blocks,
                              park=bool(req.handoff_device))
        stacked = gather_blocks_dispatch(self.kv, ids, self.cfg.kv_block_size)
        seq_hashes = list(req.seq.sequence_hashes[:req.registered_blocks])
        handoff = req.handoff
        kvh = self.wire_kv_heads

        if req.handoff_device:
            # device bulk plane: ship the gather output as device arrays —
            # no host fetch; the decode engine device_puts + scatters
            async def send() -> None:
                await handoff(tok, logprob,
                              {"stacked": stacked, "n_blocks": n_blocks},
                              seq_hashes)
        elif req.handoff_layered and all(
                getattr(v, "is_fully_addressable", True)
                for v in stacked.values()):
            # streaming layer-wise handoff (llm/kv/stream.py): hand the
            # worker per-layer fetch handles over the ONE dispatched
            # gather — layer l+1's device→host fetch overlaps layer l's
            # wire send, and the decode side scatters as frames land.
            # Multi-controller gathers keep the monolithic path (their
            # per-rank shards are assembled whole by fetch_wire).
            from .block_copy import fetch_wire_layer
            from ..llm.kv.stream import LayeredHarvest
            num_layers = next(iter(stacked.values())).shape[0]

            async def send() -> None:
                harvest = LayeredHarvest(
                    num_layers=num_layers,
                    fetch_layer=lambda l: fetch_wire_layer(
                        stacked, n_blocks, kvh, l),
                    fetch_all=lambda: fetch_wire(stacked, n_blocks, kvh))
                await handoff(tok, logprob, harvest, seq_hashes)
        else:
            async def send() -> None:
                values = await asyncio.to_thread(
                    fetch_wire, stacked, n_blocks, kvh)
                await handoff(tok, logprob, values, seq_hashes)

        task = asyncio.get_running_loop().create_task(
            send(), name=f"kv-handoff-{req.rid}")
        self._handoff_tasks.add(task)
        task.add_done_callback(self._handoff_tasks.discard)
        if not req.handoff_device:
            # device mode keeps tok/logprob as device scalars (the token
            # rides the payload; no host sync here) — emitting them would
            # hand device arrays to a queue whose contract is host values
            self._emit(req, tok, logprob)
        self._release_slot(req)
        self._finish_request(req, FinishReason.LENGTH)

    def _tables_for_dispatch(self, sit_out=None) -> np.ndarray:
        """Block tables a dispatch should see: slots that hold a request
        but take no part in it — non-ready admissions, and ``sit_out``
        slots whose in-flight token is their last — keep their mirror row
        but the DISPATCH aims them at the trash block — copy-on-write so
        the mirror survives."""
        tables = self._block_tables
        if sit_out is None:
            sit_out = self._no_slot
        for i, s in enumerate(self.slots):
            if s is not None and (not s.ready or sit_out[i]):
                if tables is self._block_tables:
                    tables = self._block_tables.copy()
                tables[i, :] = 0
        return tables

    # --------------------------------------------------------------- decode
    def _decode_step(self) -> None:
        """One decode step of whatever is ready, by the path this engine
        was built for: the ragged program; the n-gram drafter's verify step
        (drafted on the host from harvested state, harvested at once); else
        the default path, which keeps one step in flight and queues the
        next behind it: one row a slot, or the two rows of a model that
        drafts for itself (``_step_path``)."""
        if self._ragged_jit is not None:
            # ragged serving: ONE dispatch per loop iteration carries
            # every ready slot's work — pending prompt rows and due
            # decode rows together (docs/ragged_attention.md)
            self._ragged_step()
            return
        if self.drafter is not None and self._spec_candidates():
            # speculation drafts from HARVESTED state, so the in-flight
            # dispatch (if any) must drain first; spec mode therefore
            # forfeits the harvest/compute overlap — the multi-token
            # emission per dispatch is the bigger lever when drafts land
            if self._pending is not None:
                self._drain("spec")
                if not any(s is not None and s.ready for s in self.slots):
                    return
                self.clock.enter("build")
            if self._decode_step_spec():
                return
            # drafter came up dry everywhere: plain decode this step
            # (the k=0 degeneracy — speculation costs nothing when idle)
        self._decode_step_multi(self.cfg.decode_steps_per_dispatch)

    def _drain(self, cause: str) -> None:
        """Harvest the in-flight dispatch with no successor launched
        behind it: the device idles until the next fresh dispatch. Counted
        by cause (``pipeline_drains``) and marked on the harvest's flight
        record."""
        self.pipeline_drains[cause] = self.pipeline_drains.get(cause, 0) + 1
        prev, self._pending = self._pending, None
        prev["drain"] = cause
        _dispatch, harvest = self._step_path()
        harvest(prev)

    def _step_path(self) -> tuple:
        """(dispatch, harvest) of the default path's step: one row a slot,
        or the two rows (last token, draft) of a model that drafts for
        itself. Both take ``_decode_step_multi``'s way: one step in flight,
        the next queued behind it off its on-device results."""
        if self.resident_drafter:
            return self._dispatch_rows, self._harvest_verify
        return self._dispatch_multi, self._harvest

    def _decode_step_multi(self, K: int) -> None:
        """K fused decode steps, one dispatch, one host harvest: sampled
        tokens chain into the next step on device (lax.scan), so the
        device→host fetch — the dominant per-step cost on high-latency
        links — is paid once per K tokens. EOS/cancel/max_tokens are
        applied at harvest: device steps past a finish are discarded (the
        documented K-1-steps-of-waste trade, EngineConfig).

        The harvest is deferred one dispatch — always at K = 1, with
        ``decode_dispatch_pipeline`` at K > 1: the next dispatch launches
        chained off the in-flight one's ON-DEVICE tokens before the loop
        fetches them, so the fetch, the bookkeeping, the admissions'
        completion and the event loop's turn all run under a step's
        device time — steady state max(device, host) instead of their
        sum. Finish reaction widens by one dispatch (≤2K-1 steps). A
        replay recorder at K = 1 sees every step harvested before the
        next is built (the followers' stream was validated for K > 1
        only).

        A resident drafter's two-row step (K = 1; ``_step_path``) goes the
        same way: the program decides acceptance and rewind itself, so the
        step behind it needs nothing the host has not got."""
        dispatch, harvest = self._step_path()
        if self._pending is not None:
            nxt, cause = self._dispatch_pipelined(K)
            if nxt is not None:
                prev, self._pending = self._pending, nxt
                harvest(prev)
                return
            # nothing could be launched ahead (K > 1: slot churn; growth
            # that needs harvested state; every in-flight token a last
            # one): harvest, then a fresh host-fed dispatch against the
            # harvested state
            self._drain(cause)
            self.clock.enter("build")
        if not self._prepare_multi(K):
            return
        pending = dispatch(K)
        if (self.cfg.decode_dispatch_pipeline if K > 1
                else self.recorder is None):
            self._pending = pending
        else:
            harvest(pending)

    def _prepare_multi(self, K: int, ahead_mask=None,
                       sit_out=None) -> bool:
        """Capacity check + block-table pre-grow for the next K steps.
        ``ahead_mask`` flags slots whose request has K un-harvested steps
        already in flight (deferred harvest); ``sit_out`` flags slots the
        coming dispatch leaves out. Returns False when nothing is left to
        decode — or, with a mask, when the pipeline must drain before
        growth/finish decisions can be made safely (note: blocks already
        grown for earlier slots in the pass stay attached; they remain
        owned by their requests either way).

        One step grows exactly the block its write lands in (what the
        one-step path has always done, after each token instead of before
        it; a full context finishes at its harvest). K > 1 keeps a
        token of headroom beyond its K writes and finishes a sequence
        that close to its capacity before the dispatch.

        A resident drafter's step (K is 1) writes two adjacent rows: from
        harvested state at pos, pos + 1. A step in flight advances its slot
        by one position or by two, which only the device knows yet, so the
        rows lie somewhere in pos + 1 .. pos + 3: both groups grow for the
        upper bound, and the window group lets go only of what lies wholly
        behind the window of the query at the LOWER bound (a rejected row
        is rewound and rewritten by the next step's first row; the ring
        holds the whole union: HybridCacheLayout.rows_ahead)."""
        bs = self.cfg.kv_block_size
        capacity = self.M * bs
        reach = K + 1 if K > 1 else 1
        rows = self.cfg.spec_k + 1 if self.resident_drafter else 0
        if ahead_mask is None:
            ahead_mask = self._no_slot
        if sit_out is None:
            sit_out = self._no_slot
        for i, s in enumerate(self.slots):
            if s is None or not s.ready or sit_out[i]:
                continue
            in_flight = bool(ahead_mask[i])
            # lo: the lowest position the dispatch may query; hi: one past
            # the highest it may write
            if rows:
                lo, hi = s.pos + in_flight, s.pos + rows * (1 + in_flight)
            else:
                lo = s.pos + (K if in_flight else 0)
                hi = lo + reach
            if hi > capacity:
                # within K tokens of the context capacity (no position left
                # for the draft row: --max-model-len counts it): finish now
                # rather than let the scan write past the block table
                # (bounded early stop, same K-granularity trade as EOS)
                if in_flight:
                    return False
                self._release_slot(s)
                self._finish_request(s, FinishReason.LENGTH)
                continue
            need = self._blocks_needed(hi)
            if need > len(s.blocks):
                new = self.kv_manager.pool.alloc_uninit(need - len(s.blocks))
                if new is None:
                    # out of KV memory: preempt (recompute) when other
                    # sequences keep the pool contended, else finish — but
                    # never with un-harvested tokens in flight
                    if in_flight:
                        return False
                    self._preempt_or_finish(s)
                    continue
                s.blocks.extend(new)
                self._block_tables[i, :len(s.blocks)] = s.blocks
            first = lo // bs
            if self.has_window_pool and any(
                    b not in s.win.held for b in range(first, need)):
                # a write lands in a new block: let go of what the
                # window has left behind, then take the block (a step in
                # flight still reads what is let go: it runs first)
                self.kv_manager.window_slide(s.win, lo)
                if not self.kv_manager.window_grow(s.win, first, need):
                    if in_flight:
                        return False
                    self._preempt_or_finish(s)
                    continue
                self._window_ring(i, s)
        return any(s is not None and s.ready for s in self.slots)

    def _dispatch_pipelined(self, K: int) -> tuple:
        """Steady-state dispatch behind an un-harvested one: chain off the
        in-flight batch's device tokens. Returns (the new pending record,
        None), or (None, why) when the pipeline must drain first.

        At one step per dispatch chaining is per slot: a slot whose
        request is the one in flight takes its input token from the
        device and runs one position and key step ahead of harvested
        host state; a newly ready admission feeds its host-known first
        token; an emptied slot aims at the trash block. A slot whose
        in-flight token is known to be its last (token budget, context
        capacity, a cancel already seen) sits the dispatch out, so such
        a finish wastes nothing; a finish by EOS or stop discards one
        slot-row at its harvest. A two-row step in flight advances its
        slot by one position or by two: the slot sits out where even one
        is its last, and rides where only the second would be (its rows
        are then discarded at their harvest).

        K > 1 chains all or nothing: the slot→request mapping must be
        IDENTICAL to the in-flight dispatch's, and any churn (admission,
        finish, preemption, re-admission) drains the pipeline and
        restarts it from harvested host state."""
        prev = self._pending
        now = [s if (s is not None and s.ready) else None
               for s in self.slots]
        same = [s is r for s, r in zip(now, prev["reqs"])]
        mask = np.array([s is not None and m for s, m in zip(now, same)],
                        dtype=bool)
        sit_out = self._no_slot
        if K > 1:
            if prev["K"] != K or not all(same):
                return None, "slot_churn"
        else:
            # no room for the next step's rows even one position on
            rows = self.cfg.spec_k + 1 if self.resident_drafter else 1
            full = self.M * self.cfg.kv_block_size - rows
            sit_out = np.array(
                [bool(m) and (s.generated + 1 >= s.max_new_tokens
                              or s.pos >= full or s.cancelled)
                 for m, s in zip(mask, now)], dtype=bool)
            mask &= ~sit_out
            if not any(s is not None and not o
                       for s, o in zip(now, sit_out)):
                return None, "last_token"
        if not self._prepare_multi(K, ahead_mask=mask, sit_out=sit_out):
            return None, "kv_growth"
        dispatch, _harvest = self._step_path()
        return dispatch(K, chain=prev["chain"], mask=mask, sit_out=sit_out,
                        chained_from=prev.get("id")), None

    def _dispatch_multi(self, K: int, chain=None, mask=None,
                        sit_out=None, chained_from=None) -> dict:
        """Launch one K-step dispatch. ``mask`` flags slots chained off
        the in-flight dispatch: their input token comes from ``chain``
        (its [K, B] device tokens) and their positions/keys run K steps
        ahead of harvested host state; everything else feeds host-known
        last_tokens, except ``sit_out`` slots, which take no part."""
        if mask is None:
            mask = self._no_slot
        if sit_out is None:
            sit_out = self._no_slot
        riders = [s if (s is not None and s.ready and not sit_out[i])
                  else None for i, s in enumerate(self.slots)]
        steps = np.zeros((self.B,), np.int64)
        for i, s in enumerate(riders):
            ahead = K if mask[i] else 0
            if s is None:
                self._tokens[i] = 0
                self._positions[i] = 0
                if self.slots[i] is None:
                    self._block_tables[i, :] = 0  # trash block
            else:
                self._tokens[i] = s.last_token
                self._positions[i] = s.pos + ahead
                steps[i] = s.key_step + ahead
        tables = self._tables_for_dispatch(sit_out)
        # lane-prefill planned inputs: stateless from positions (which
        # already include the pipelined +K lookahead), so chained and
        # host-fed dispatches agree without extra bookkeeping. The common
        # no-lanes case reuses cached device-resident zeros (no per-dispatch
        # host allocation/transfer on the latency-sensitive path).
        planned = pmask = None
        for i, s in enumerate(riders):
            if s is None or s.lane_prompt is None:
                continue
            if planned is None:
                planned = np.zeros((K, self.B), np.int32)
                pmask = np.zeros((K, self.B), bool)
            pos0 = int(self._positions[i])
            n_pr = len(s.lane_prompt)
            for k in range(K):
                p = pos0 + k
                if p < n_pr:
                    planned[k, i] = s.lane_prompt[p]
                    pmask[k, i] = True
        self._step += K
        host_tokens = _owned(self._tokens)
        tokens_in = (self._merge_jit(chain, host_tokens, jnp.asarray(mask))
                     if chain is not None else host_tokens)
        did = None
        if self.recorder is not None:
            did = self.recorder.next_dispatch_id()
            self.recorder.rec(
                "dispatch", id=did, K=K,
                chained_from=chained_from if chain is not None else None,
                mask=mask.copy(), tokens=self._tokens.copy(),
                positions=self._positions.copy(), tables=tables.copy(),
                seeds=self._seeds.copy(), steps=steps.copy(),
                temperature=self._samp["temperature"].copy(),
                top_k=self._samp["top_k"].copy(),
                top_p=self._samp["top_p"].copy(),
                **({"planned": planned.copy(),
                    "planned_mask": pmask.copy()}
                   if planned is not None else {}),
                reqs=[s.rid if s is not None else None for s in riders])
        if planned is None:
            planned_dev, pmask_dev = self._planned_zero
        else:
            planned_dev, pmask_dev = jnp.asarray(planned), jnp.asarray(pmask)
        args = (tokens_in, _owned(self._positions), _owned(tables),
                _owned(self._seeds), jnp.asarray(steps),
                _owned(self._samp["temperature"]),
                _owned(self._samp["top_k"]), _owned(self._samp["top_p"]),
                planned_dev, pmask_dev, self._base_key)
        self.clock.enter("dispatch")
        toks_k, logprobs_k, self.kv = self._decode_k_jit(
            self.params, self.kv, *args)
        self.clock.enter("build")
        return {"toks": toks_k, "logprobs": logprobs_k, "K": K, "id": did,
                "chain": toks_k, "reqs": riders, "mask": mask,
                **self._key_wave_counts(tables, riders, K)}

    def _key_wave_counts(self, tables: np.ndarray, riders: list,
                         K: int) -> dict:
        """{key_waves, key_run_waves} of one dispatch of a model with an
        indexer ({} otherwise): the waves the ``index_scores`` kernel walks
        over the riding slots' index keys in one layer, summed over the K
        steps, and those of them whose blocks lie adjacent in the pool
        (one copy, not one per block). The kernel's own predicate and
        depth on the dispatch's numpy tables, with the last layer's bound
        at the pool's end; whole-array arithmetic, no walk over slots."""
        chunk = self._key_wave_blocks
        if not chunk:
            return {}
        bsz = self.cfg.kv_block_size
        riding = np.array([s is not None for s in riders])
        waves = run_waves = 0
        for k in range(1, K + 1):
            lens = np.where(riding, self._positions + k, 0)
            walked = -(-lens // (chunk * bsz))                       # [B]
            contig = wave_contig_table(
                tables[:, :self.M], lens, block_size=bsz, chunk=chunk,
                pool_blocks=self.kv["idx"].shape[1] // bsz, xp=np)
            waves += int(walked.sum())
            run_waves += int(contig[np.arange(contig.shape[1])[None, :]
                                    < walked[:, None]].sum())
        return {"key_waves": waves, "key_run_waves": run_waves}

    def _harvest(self, pending: dict) -> None:
        """Apply one dispatch's results: emissions, seq bookkeeping,
        EOS/budget/cancel finishes. Device overrun past a finish — or past
        a slot whose request changed since dispatch — is discarded."""
        from ..runtime.faults import hit as _fault
        _fault("engine.harvest")    # chaos: loop-fatal boundary — an
        # injected error here kills the loop LOUDLY and _fail_pending
        # releases every slot/hold (asserted in tests/test_chaos.py)
        self.clock.enter("wait")
        toks_k = np.asarray(pending["toks"])       # [K, B] — ONE host fetch
        logprobs_k = np.asarray(pending["logprobs"])
        self.clock.enter("post")
        K = pending["K"]
        capacity = self.M * self.cfg.kv_block_size
        applied = []
        # what attention had to cover: the live context of every step
        # applied (ctx_tokens) and, under deepseek_v32's selection, the
        # rows it is configured to read of it (sel_tokens; the same number
        # with no indexer). Host arithmetic on positions: a descriptor of
        # the traffic for cost models, not a reading of the device
        topk = self.model_cfg.index_topk
        # win_tokens: the rows a window layer reads of that context
        # (min(context, window); the same number on a model without window
        # layers of bounded rows). state_bytes: the recurrent state the
        # applied slot-steps read and write (0 on every other model)
        window = self._window
        ctx_tokens = sel_tokens = win_tokens = steps_applied = 0
        for i, req in enumerate(pending["reqs"]):
            if req is None or self.slots[i] is not req:
                continue
            n_applied = 0
            pos0 = req.pos
            input_tok = req.last_token
            for k in range(K):
                if req.cancelled:
                    self._release_slot(req)
                    self._finish_request(req, FinishReason.CANCELLED)
                    break
                in_prompt = (req.lane_prompt is not None
                             and req.pos < len(req.lane_prompt))
                if in_prompt:
                    input_tok = req.lane_prompt[req.pos]
                tok = int(toks_k[k, i])
                if req.seq is not None:
                    req.seq.append(input_tok)
                    req.registered_blocks = \
                        self.kv_manager.register_full_blocks(
                            req.blocks, req.seq, req.registered_blocks,
                            tenant=req.tenant or None)
                    if self.has_window_pool:
                        self.kv_manager.window_register(
                            req.win, req.seq, req.blocks,
                            len(req.seq.tokens))
                req.pos += 1
                req.key_step += 1
                n_applied += 1
                if in_prompt and req.pos < len(req.lane_prompt):
                    # mid-prompt planned step: the sampled token is
                    # discarded; the next input comes from the prompt
                    self.total_prefill_tokens += 1
                    continue
                if in_prompt:               # consumed the LAST prompt token
                    self.total_prefill_tokens += 1
                    req.lane_prompt = None  # plain decode from here on
                req.generated += 1
                req.last_token = tok
                self.total_decode_tokens += 1
                self._mark_first_token(req)
                self._emit(req, tok, float(logprobs_k[k, i]))
                if req.pos >= capacity:
                    # the context is full: no position left to write the
                    # next input's KV (one step per dispatch only; K > 1
                    # stops short of it in _prepare_multi)
                    self._release_slot(req)
                    self._finish_request(req, FinishReason.LENGTH)
                else:
                    self._maybe_finish_after_emit(req)
                if self.slots[i] is not req:
                    break                      # finished: drop device overrun
                input_tok = tok
            applied.append((i, req.rid, n_applied))
            steps_applied += n_applied
            for ctx in range(pos0 + 1, pos0 + 1 + n_applied):
                ctx_tokens += ctx
                sel_tokens += min(ctx, topk) if topk else ctx
                win_tokens += min(ctx, window) if window else ctx
        if self.recorder is not None and pending.get("id") is not None:
            self.recorder.rec("harvest", id=pending["id"],
                              toks=toks_k.copy(), applied=applied)
        # flight record: one line per dispatch-harvest cycle. device_ms is
        # the cycle's wait phase (what the loop actually blocked on the
        # device); host_gap_ms is everything since the last cycle ended
        # that was NOT that wait, and the <phase>_ms fields say what:
        # admission, input build, dispatch, bookkeeping, the event loop.
        # chained: of batch_fill, the slots this dispatch fed from the
        # device behind an un-harvested one; drain: why no successor was
        # launched behind it, where none was
        self.flight.record_cycle(
            "decode", K=K,
            batch_fill=len(applied),
            chained=sum(1 for i, _r, _n in applied if pending["mask"][i]),
            planned_tokens=K * len(applied),
            emitted=sum(n for _i, _r, n in applied),
            ctx_tokens=ctx_tokens, sel_tokens=sel_tokens,
            win_tokens=win_tokens,
            # window-pool blocks the fullest slot holds (a layer's; the
            # bound is the ring: R1 of docs/hybrid_cache.md); 0 elsewhere
            win_blocks_live=max(
                (len(r.win.held) for r in self.slots
                 if r is not None and r.win is not None), default=0)
            if self.has_window_pool else 0,
            state_bytes=steps_applied * self._step_state_bytes,
            **{k: pending[k] for k in ("key_waves", "key_run_waves", "drain")
               if k in pending})

    # --------------------------------------------------------------- ragged
    def _ragged_step(self) -> None:
        """One unified ragged dispatch (engine/ragged.py): pack every
        ready slot's pending work — mid-prompt lanes contribute up to
        ragged_max_seq_rows prompt rows, decoding slots one chained
        token row or, with spec_k, a [1+k]-row speculative span — into
        a single token-capacity-filled batch, dispatch the ONE compiled
        ragged program, harvest.

        With ``decode_dispatch_pipeline`` a pure-decode dispatch defers
        its harvest one iteration: the next dispatch chains off the
        in-flight device tokens (the chained-sample merge — each
        chained row takes the previous dispatch's token at its slot's
        sample row), so the device→host fetch overlaps the next
        dispatch's compute exactly like the fused decode pipeline. Any
        churn — admissions, prefill lanes, spec drafts (which draft
        from HARVESTED history, the split path's rule), slot turnover,
        growth failure — drains the pipeline first and costs one
        un-overlapped dispatch.

        Block growth runs BEFORE packing at each slot's maximum
        possible row count this dispatch (the packer only ever shrinks
        a span, and over-grown blocks stay owned by their request —
        the _prepare_multi precedent); a slot that cannot grow preempts
        or finishes exactly as the split path would."""
        if self._ragged_pending is not None:
            nxt = self._ragged_dispatch_pipelined()
            prev, self._ragged_pending = self._ragged_pending, None
            self._harvest_ragged(prev)
            if nxt is not None:
                self._ragged_pending = nxt
                return
            if not any(s is not None and s.ready for s in self.slots):
                return
            # couldn't chain (churn / drafts due / growth failure):
            # fall through to a fresh host-fed dispatch against the
            # harvested state
            self.clock.enter("build")
        pending = self._ragged_dispatch_fresh()
        if pending is None:
            return
        if (self.cfg.decode_dispatch_pipeline
                and all(sq.mode == "decode"
                        for sq in pending["batch"].seqs)):
            # pure-decode dispatch: defer the harvest so the next
            # iteration can chain off it (prefill/spec spans harvest
            # synchronously — their bookkeeping gates the next packing)
            self._ragged_pending = pending
        else:
            self._harvest_ragged(pending)

    def _ragged_draft(self) -> Dict[int, tuple]:
        """Host-side n-gram drafts for every decoding slot with a live
        spec budget — the spec spans this dispatch will carry. Drafting
        reads HARVESTED history only (the _decode_step_spec rule), so
        the caller must have drained any pipelined dispatch."""
        drafts: Dict[int, tuple] = {}
        if self.drafter is None:
            return drafts
        for i, s in enumerate(self.slots):
            if (s is None or not s.ready or s.seq is None
                    or s.last_token < 0):
                continue
            if s.lane_prompt is not None and s.pos < len(s.lane_prompt):
                continue               # mid-prompt: decode hasn't begun
            k = self._req_spec_k(s)
            if k <= 0:
                continue
            d = self.drafter.draft(list(s.seq.tokens) + [s.last_token],
                                   k)
            if d:
                drafts[i] = (s, [int(t) for t in d[:k]])
        return drafts

    def _ragged_dispatch_fresh(self) -> Optional[dict]:
        """Draft, grow, pack and launch one host-fed ragged dispatch.
        Returns the pending record (un-harvested), or None when nothing
        was dispatched."""
        from .ragged import build_ragged_batch
        cfg = self.cfg
        Lmax = cfg.ragged_max_seq_rows
        capacity = self.M * cfg.kv_block_size
        drafts = self._ragged_draft()
        for i, s in enumerate(self.slots):
            if s is None or not s.ready:
                continue
            in_prompt = (s.lane_prompt is not None
                         and s.pos < len(s.lane_prompt))
            ent = drafts.get(i)
            n_draft = (len(ent[1]) if ent is not None and ent[0] is s
                       else 0)
            want = (min(len(s.lane_prompt) - s.pos, Lmax) if in_prompt
                    else 1 + n_draft)
            if s.pos + want + 1 > capacity:
                self._release_slot(s)
                self._finish_request(s, FinishReason.LENGTH)
                continue
            need = self._blocks_needed(s.pos + want + 1)
            if need > len(s.blocks):
                new = self.kv_manager.pool.alloc_uninit(
                    need - len(s.blocks))
                if new is None:
                    self._preempt_or_finish(s)
                    continue
                s.blocks.extend(new)
                self._block_tables[i, :len(s.blocks)] = s.blocks

        decode_rows = []
        prefill_lanes = []
        spec_lanes = []
        for i, s in enumerate(self.slots):
            if s is None or not s.ready:
                continue
            if s.lane_prompt is not None and s.pos < len(s.lane_prompt):
                prefill_lanes.append(
                    (i, s.lane_prompt[s.pos:s.pos + Lmax], s.pos))
                continue
            ent = drafts.get(i)
            # growth may have preempted/finished the drafted request —
            # keep drafts only for slots that still hold it
            if ent is not None and ent[0] is s:
                spec_lanes.append((i, [s.last_token] + ent[1], s.pos))
            else:
                decode_rows.append((i, s.last_token, s.pos))
        batch = build_ragged_batch(cfg.ragged_max_tokens, self.B,
                                   decode_rows, prefill_lanes, Lmax,
                                   spec_lanes=spec_lanes)
        if batch is None:
            return None
        return self._ragged_dispatch(batch)

    def _ragged_dispatch_pipelined(self) -> Optional[dict]:
        """Steady-state pipelined ragged dispatch: chain off the
        in-flight dispatch's device tokens. Returns the new pending
        record, or None when the pipeline must drain first (the
        _dispatch_pipelined contract: any churn restarts from harvested
        host state)."""
        prev = self._ragged_pending
        now = [s if (s is not None and s.ready) else None
               for s in self.slots]
        if any(now[i] is not prev["reqs"][i] for i in range(self.B)):
            return None
        live = [i for i in range(self.B) if now[i] is not None]
        if not live:
            return None
        for i in live:
            s = now[i]
            if s.lane_prompt is not None and s.pos < len(s.lane_prompt):
                return None        # admission churn mid-flight
            if (self.drafter is not None and s.seq is not None
                    and self._req_spec_k(s) > 0):
                # speculation drafts from HARVESTED state — drain, the
                # next fresh dispatch carries the spec span (the split
                # path forfeits the overlap the same way)
                return None
        # capacity/growth one token ahead; never finish/preempt with an
        # un-harvested token in flight — drain instead
        capacity = self.M * self.cfg.kv_block_size
        from .ragged import build_ragged_batch
        for i in live:
            s = now[i]
            if s.pos + 1 + 2 > capacity:
                return None
            need = self._blocks_needed(s.pos + 1 + 2)
            if need > len(s.blocks):
                new = self.kv_manager.pool.alloc_uninit(
                    need - len(s.blocks))
                if new is None:
                    return None
                s.blocks.extend(new)
                self._block_tables[i, :len(s.blocks)] = s.blocks
        batch = build_ragged_batch(
            self.cfg.ragged_max_tokens, self.B,
            [(i, now[i].last_token, now[i].pos + 1) for i in live],
            [], self.cfg.ragged_max_seq_rows)
        if batch is None:
            return None
        return self._ragged_dispatch(batch, chain=prev, ahead=1)

    def _ragged_dispatch(self, batch, chain: Optional[dict] = None,
                         ahead: int = 0) -> dict:
        """Launch one ragged dispatch over ``batch``. ``chain`` is the
        in-flight pending record whose device tokens feed this
        dispatch's decode rows (the chained-sample merge); ``ahead``
        is how many un-harvested tokens each chained slot runs ahead
        of host state (positions/key_steps were already advanced by
        the caller's packing). Returns the pending record."""
        cfg = self.cfg
        seeds = np.zeros((self.B + 1,), np.int64)
        temp = np.zeros((self.B + 1,), np.float32)
        top_k = np.zeros((self.B + 1,), np.int32)
        top_p = np.ones((self.B + 1,), np.float32)
        seeds[:self.B] = self._seeds
        temp[:self.B] = self._samp["temperature"]
        top_k[:self.B] = self._samp["top_k"]
        top_p[:self.B] = self._samp["top_p"]
        if self._ragged_row_sampled:
            # ROW steps: row r of a span keys at key_step + r — the
            # verify program's lockstep discipline; at a span's last
            # row this is the slot-sampled key by the skew convention
            steps = np.zeros((cfg.ragged_max_tokens,), np.int64)
            for sq in batch.seqs:
                s = self.slots[sq.slot]
                steps[sq.start:sq.start + sq.length] = (
                    s.key_step + ahead + np.arange(sq.length))
        else:
            steps = np.zeros((self.B + 1,), np.int64)
            for sq in batch.seqs:
                s = self.slots[sq.slot]
                # the LAST row of a span samples at the key_step the
                # split path would use there: lane's skew convention
                # makes that key_step + len - 1 (== key_step for
                # decode rows)
                steps[sq.slot] = s.key_step + ahead + sq.length - 1
        tables = np.zeros((self.B + 1, self.M), np.int32)
        tables[:self.B] = self._tables_for_dispatch()
        mask = srows = None
        if chain is not None:
            # chained-sample merge: each chained row takes the previous
            # dispatch's device token at its slot's sample row
            prev_batch = chain["batch"]
            mask = np.zeros((cfg.ragged_max_tokens,), bool)
            srows = np.zeros((cfg.ragged_max_tokens,), np.int32)
            for sq in batch.seqs:
                mask[sq.start] = True
                srows[sq.start] = (
                    int(prev_batch.sample_rows[sq.slot])
                    if self._ragged_row_sampled else sq.slot)
        self._step += 1
        did = None
        if self.recorder is not None:
            did = self.recorder.next_dispatch_id()
            self.recorder.rec(
                "ragged", id=did, tokens=batch.tokens.copy(),
                positions=batch.positions.copy(),
                row_slot=batch.row_slot.copy(),
                starts=batch.seq_starts.copy(),
                counts=batch.seq_counts.copy(),
                sample_rows=batch.sample_rows.copy(),
                tables=tables.copy(), seeds=seeds.copy(),
                steps=steps.copy(), temperature=temp.copy(),
                top_k=top_k.copy(), top_p=top_p.copy(),
                seqs=batch.seqs_meta(),
                chained_from=(chain["id"] if chain is not None
                              else None),
                mask=(mask.copy() if mask is not None else None),
                srows=(srows.copy() if srows is not None else None),
                reqs=[s.rid if (s is not None and s.ready) else None
                      for s in self.slots])
        # jnp.array COPIES the host mirrors (the _dispatch_multi
        # aliasing note): a deferred-harvest dispatch may still be
        # executing while the next iteration mutates them
        host_tokens = jnp.array(batch.tokens)
        if chain is not None:
            tokens_in = self._ragged_merge_jit(
                chain["toks"], jnp.array(srows), host_tokens,
                jnp.array(mask))
        else:
            tokens_in = host_tokens
        args = (tokens_in, jnp.array(batch.positions),
                jnp.array(tables), jnp.array(batch.row_slot),
                jnp.array(batch.seq_starts),
                jnp.array(batch.seq_counts),
                jnp.array(batch.sample_rows),
                jnp.array(seeds), jnp.array(steps),
                jnp.array(temp), jnp.array(top_k), jnp.array(top_p))
        self.clock.enter("dispatch")
        toks, logprobs, self.kv = self._ragged_jit(
            self.params, self.kv, *args)
        self.clock.enter("build")
        self.ragged_dispatches += 1
        self.ragged_rows_total += batch.rows_used
        self.ragged_prefill_rows_total += batch.prefill_rows
        self.ragged_decode_rows_total += (batch.rows_used
                                          - batch.prefill_rows)
        if batch.mixed:
            self.ragged_mixed_dispatches += 1
        self.ragged_dispatches_saved += batch.dispatches_replaced - 1
        if batch.n_spec:
            self.spec_dispatches += 1
            self.spec_drafted_tokens += batch.spec_rows
            self.ragged_spec_rows += batch.spec_rows
        # cross-sequence wave prefetch accounting: the host-side mirror
        # of the kernel's parity chain over THIS dispatch's geometry
        # (attention.ragged_prefetch_counts — honest on CPU, where the
        # XLA fallback runs no kernel; the global-layer walk)
        from .attention import ragged_prefetch_counts
        pf = ragged_prefetch_counts(
            batch.seq_counts, batch.positions[batch.sample_rows] + 1,
            block_size=cfg.kv_block_size, blocks_per_table=self.M)
        self.ragged_first_waves += pf["first_waves"]
        self.ragged_prefetched_waves += pf["prefetched"]
        return {"batch": batch, "toks": toks, "logprobs": logprobs,
                "id": did, "prefetch": pf, "chained": chain is not None,
                "reqs": [s if (s is not None and s.ready) else None
                         for s in self.slots]}

    def _harvest_ragged(self, pending: dict) -> None:
        """Apply one ragged dispatch: per span, the consumed prompt
        rows' bookkeeping (hash chain, registration, pos/key_step —
        exactly the lane harvest's per-token walk) and, when the span
        ends in a sample (decode row, or the row consuming the LAST
        prompt token), the emission + finish checks of one decode
        step. Speculative spans walk their rows with LOCKSTEP
        acceptance (the _harvest_verify discipline verbatim: rejected
        draft rows roll back by rewind — pos never advances over them,
        and later dispatches rewrite every stale row before any query
        attends it).

        ``applied`` entries are (slot, rid, rows_applied, emitted) —
        emitted is a COUNT (spec spans emit one token per applied
        row)."""
        self.clock.enter("wait")
        # [B+1] slot samples, or [capacity] row samples in the
        # spec-enabled row-sampled variant — ONE fetch either way
        toks = np.asarray(pending["toks"])
        logprobs = np.asarray(pending["logprobs"])
        self.clock.enter("post")
        batch = pending["batch"]
        row_sampled = self._ragged_row_sampled
        applied = []
        for sq in batch.seqs:
            i = sq.slot
            req = pending["reqs"][i]
            if req is None or self.slots[i] is not req:
                continue
            if req.cancelled:
                self._release_slot(req)
                self._finish_request(req, FinishReason.CANCELLED)
                continue
            if sq.mode == "spec":
                # lockstep-acceptance walk over the span's rows: row t
                # wrote inputs[t]'s KV — one decode step's bookkeeping;
                # reaching row t>0 accepted draft t
                inputs = batch.tokens[sq.start:sq.start + sq.length]
                n_applied = 0
                for t in range(sq.length):
                    tok = int(toks[sq.start + t])
                    req.seq.append(int(inputs[t]))
                    req.registered_blocks = \
                        self.kv_manager.register_full_blocks(
                            req.blocks, req.seq, req.registered_blocks,
                            tenant=req.tenant or None)
                    req.pos += 1
                    req.key_step += 1
                    req.generated += 1
                    req.last_token = tok
                    n_applied += 1
                    self.total_decode_tokens += 1
                    self.spec_emitted_tokens += 1
                    if t > 0:
                        self.spec_accepted_tokens += 1
                    self._mark_first_token(req)
                    self._emit(req, tok, float(logprobs[sq.start + t]))
                    self._maybe_finish_after_emit(req)
                    if self.slots[i] is not req:
                        break      # finished: drop the overrun rows
                    if (t + 1 < sq.length
                            and tok != int(inputs[t + 1])):
                        break      # draft rejected: rewind-rollback
                applied.append((i, req.rid, n_applied, n_applied))
                continue
            if sq.mode == "prefill":
                for t in range(sq.length):
                    req.seq.append(req.lane_prompt[req.pos])
                    req.registered_blocks = \
                        self.kv_manager.register_full_blocks(
                            req.blocks, req.seq, req.registered_blocks,
                            tenant=req.tenant or None)
                    req.pos += 1
                    req.key_step += 1
                self.total_prefill_tokens += sq.length
                if req.pos < len(req.lane_prompt):
                    applied.append((i, req.rid, sq.length, 0))
                    continue               # still mid-prompt: no sample
                req.lane_prompt = None     # plain decode from here on
            else:
                req.seq.append(int(req.last_token))
                req.registered_blocks = \
                    self.kv_manager.register_full_blocks(
                        req.blocks, req.seq, req.registered_blocks,
                        tenant=req.tenant or None)
                req.pos += 1
                req.key_step += 1
                self.total_decode_tokens += 1
            sample = (sq.start + sq.length - 1) if row_sampled else i
            tok = int(toks[sample])
            req.generated += 1
            req.last_token = tok
            self._mark_first_token(req)
            self._emit(req, tok, float(logprobs[sample]))
            self._maybe_finish_after_emit(req)
            applied.append((i, req.rid, sq.length, 1))
        if self.recorder is not None and pending.get("id") is not None:
            self.recorder.rec("ragged_harvest", id=pending["id"],
                              toks=toks.copy(), applied=applied)
        # per-dispatch mode mix rides the flight recorder ring — the
        # /debug + llmctl trace dump view of how full, how mixed, how
        # speculative, and how well-prefetched each ragged dispatch ran
        pf = pending.get("prefetch") or {}
        self.flight.record_cycle(
            "ragged", rows=batch.rows_used,
            capacity=batch.capacity,
            fill=round(batch.fill_ratio, 4),
            prefill_rows=batch.prefill_rows,
            decode_rows=batch.rows_used - batch.prefill_rows,
            n_prefill=batch.n_prefill, n_decode=batch.n_decode,
            n_spec=batch.n_spec, spec_rows=batch.spec_rows,
            prefetch_first_waves=pf.get("first_waves", 0),
            prefetch_hits=pf.get("prefetched", 0),
            chained=bool(pending.get("chained")),
            mixed=batch.mixed,
            emitted=sum(e for _i, _r, _n, e in applied))

    # ---------------------------------------------------------- speculation
    def _req_spec_k(self, req: EngineRequest) -> int:
        """Effective draft budget for one request: its own knob (-1 =
        engine default, live-tunable via llmctl spec set-k) clamped to
        the compiled verify program's shape."""
        k = self.spec_k_live if req.spec_k < 0 else req.spec_k
        return max(0, min(int(k), self.cfg.spec_k))

    def _spec_candidates(self) -> bool:
        """True when a verify dispatch could be worth attempting. A
        mid-lane-prefill slot vetoes the whole batch: lanes feed planned
        prompt tokens through the K-step scan and the verify program has
        no planned-token plumbing — lanes last a handful of steps, after
        which speculation resumes."""
        any_spec = False
        for s in self.slots:
            if s is None or not s.ready:
                continue
            if s.lane_prompt is not None:
                return False
            if s.seq is not None and self._req_spec_k(s) > 0:
                any_spec = True
        return any_spec

    def _decode_step_spec(self) -> bool:
        """One speculative step: draft per slot (host-side n-gram lookup
        over the request's own history), score every slot's k drafts + 1
        bonus position in ONE verify dispatch, harvest with lockstep
        acceptance. Slots without drafts ride along as 1-row decode.
        Returns False when no slot drafted anything — the caller then
        runs the plain decode path (k=0 degeneracy)."""
        drafts: Dict[int, tuple] = {}
        for i, s in enumerate(self.slots):
            if (s is None or not s.ready or s.seq is None
                    or s.last_token < 0):
                continue
            k = self._req_spec_k(s)
            if k <= 0:
                continue
            d = self.drafter.draft(list(s.seq.tokens) + [s.last_token], k)
            if d:
                drafts[i] = (s, [int(t) for t in d[:k]])
        if not drafts:
            return False
        Tv = self.cfg.spec_k + 1
        if not self._prepare_multi(Tv):
            return True            # capacity churn consumed the step
        steps = np.zeros((self.B,), np.int64)
        tokens = np.zeros((self.B, Tv), np.int32)
        n_rows = np.zeros((self.B,), np.int32)
        dmap: Dict[int, List[int]] = {}
        for i in range(self.B):
            s = self.slots[i]
            if s is None or not s.ready:
                self._tokens[i] = 0
                self._positions[i] = 0
                if s is None:
                    self._block_tables[i, :] = 0  # trash block
                continue
            ent = drafts.get(i)
            # _prepare_multi may have finished/preempted the drafted
            # request — only keep drafts whose slot still holds it
            d = ent[1] if (ent is not None and ent[0] is s) else []
            self._tokens[i] = s.last_token
            self._positions[i] = s.pos
            steps[i] = s.key_step
            tokens[i, 0] = s.last_token
            if d:
                tokens[i, 1:1 + len(d)] = d
                dmap[i] = d
            n_rows[i] = 1 + len(d)
        if not dmap:
            return False           # every drafted slot churned away
        tables = self._tables_for_dispatch()
        self._step += 1
        did = None
        if self.recorder is not None:
            did = self.recorder.next_dispatch_id()
            self.recorder.rec(
                "verify", id=did, Tv=Tv, tokens=tokens.copy(),
                positions=self._positions.copy(), tables=tables.copy(),
                seeds=self._seeds.copy(), steps=steps.copy(),
                temperature=self._samp["temperature"].copy(),
                top_k=self._samp["top_k"].copy(),
                top_p=self._samp["top_p"].copy(),
                n_rows=n_rows.copy(),
                reqs=[s.rid if (s is not None and s.ready) else None
                      for s in self.slots])
        args = (jnp.asarray(tokens),
                jnp.asarray(self._positions), jnp.asarray(tables),
                jnp.asarray(self._seeds), jnp.asarray(steps),
                jnp.asarray(self._samp["temperature"]),
                jnp.asarray(self._samp["top_k"]),
                jnp.asarray(self._samp["top_p"]))
        self.clock.enter("dispatch")
        toks_T, lps_T, self.kv = self._verify_jit(
            self.params, self.kv, *args)
        self.spec_dispatches += 1
        self._harvest_verify({
            "toks": toks_T, "logprobs": lps_T, "drafts": dmap, "id": did,
            "reqs": [s if (s is not None and s.ready) else None
                     for s in self.slots]})
        return True

    def _dispatch_rows(self, K: int, chain=None, mask=None, sit_out=None,
                       chained_from=None) -> dict:
        """``_dispatch_multi`` for a model that drafts for itself: launch
        the two-row program (``_verify_jit`` in its resident form) over
        rows (last token, draft) of every riding slot. ``mask`` flags the
        slots chained off the dispatch in flight: the program takes their
        pair, their advance and their key steps from ``chain``, that
        dispatch's on-device carry, and the host's values (harvested
        state, one step behind) are only what it adds to; every other slot
        feeds host-known rows. Every decoding slot has a draft (a prefill,
        a hit's chunk and a preemption's re-prefill each return one): no
        slot rides along as a one-row step."""
        Tv = self.cfg.spec_k + 1
        if mask is None:
            mask = self._no_slot
        if sit_out is None:
            sit_out = self._no_slot
        riders = [s if (s is not None and s.ready and not sit_out[i])
                  else None for i, s in enumerate(self.slots)]
        steps = np.zeros((self.B,), np.int64)
        tokens = np.zeros((self.B, Tv), np.int32)
        for i, s in enumerate(riders):
            if s is None:
                self._positions[i] = 0
                if self.slots[i] is None:
                    self._block_tables[i, :] = 0  # trash block
                continue
            tokens[i] = (s.last_token, max(int(s.draft), 0))
            self._positions[i] = s.pos
            steps[i] = s.key_step
        tables = self._tables_for_dispatch(sit_out)
        self._step += 1
        did = None
        if self.recorder is not None:
            # harvested at once, so never chained: the record is the whole
            # step (replay.exec_verify_event passes the zero carry)
            did = self.recorder.next_dispatch_id()
            self.recorder.rec(
                "verify", id=did, Tv=Tv, tokens=tokens.copy(),
                positions=self._positions.copy(), tables=tables.copy(),
                seeds=self._seeds.copy(), steps=steps.copy(),
                temperature=self._samp["temperature"].copy(),
                top_k=self._samp["top_k"].copy(),
                top_p=self._samp["top_p"].copy(),
                n_rows=np.where([s is not None for s in riders], Tv,
                                0).astype(np.int32),
                reqs=[s.rid if s is not None else None for s in riders])
        carry, mask_dev = ((chain, jnp.asarray(mask)) if chain is not None
                           else self._carry_zero)
        args = (jnp.asarray(tokens), _owned(self._positions),
                _owned(tables), _owned(self._seeds), jnp.asarray(steps),
                _owned(self._samp["temperature"]),
                _owned(self._samp["top_k"]), _owned(self._samp["top_p"]),
                carry, mask_dev)
        self.clock.enter("dispatch")
        toks_T, lps_T, self.kv, drafts, carry = self._verify_jit(
            self.params, self.kv, *args)
        self.clock.enter("build")
        self.spec_dispatches += 1
        return {"toks": toks_T, "logprobs": lps_T, "next_drafts": drafts,
                "chain": carry, "K": K, "id": did, "reqs": riders,
                "mask": mask}

    def _harvest_verify(self, pending: dict) -> None:
        """Apply one verify dispatch: walk each slot's sampled rows with
        lockstep acceptance (spec/drafter.py accept_lockstep semantics,
        inlined here because each accepted row also carries one decode
        step's bookkeeping). Rejected draft rows roll back by REWIND:
        ``pos`` never advances over them, and every later dispatch
        rewrites a stale row before any query attends it (the same
        write-then-read ordering plain decode relies on).

        A resident drafter's step (``next_drafts``) is harvested under the
        NEXT step's device time: that step was queued behind this one with
        acceptance decided by the program itself (``decode_mtp``'s carry),
        and the walk below reaches the same verdict from the fetched
        tokens, since a slot's rows were (last token, draft) of the state
        the harvest before this one left. The n-gram drafter's step is
        harvested at once."""
        self.clock.enter("wait")
        toks_T = np.asarray(pending["toks"])       # [B, Tv] — ONE fetch
        lps_T = np.asarray(pending["logprobs"])
        # a resident drafter's step: the guess behind each row's sample
        next_drafts = pending.get("next_drafts")
        if next_drafts is not None:
            next_drafts = np.asarray(next_drafts)
        self.clock.enter("post")
        capacity = self.M * self.cfg.kv_block_size
        window = self._window
        ctx_tokens = win_tokens = rows = 0
        applied = []
        for i, req in enumerate(pending["reqs"]):
            if req is None or self.slots[i] is not req:
                continue
            d = ([max(int(req.draft), 0)] if next_drafts is not None
                 else pending["drafts"].get(i, []))
            inputs = [req.last_token] + d
            # drafts scored: of the slots harvested (a chained slot that
            # finished under its last step rode in vain and counts nowhere)
            self.spec_drafted_tokens += len(d)
            n_applied = 0
            accepted = 0
            # what the step's rows read of this slot, each cached row once:
            # the context up to its last row, and of it the window's reach
            # of every row
            ctx = req.pos + len(inputs)
            ctx_tokens += ctx
            win_tokens += min(ctx, window + len(inputs) - 1) if window \
                else ctx
            rows += len(inputs)
            for t in range(len(inputs)):
                if req.cancelled:
                    self._release_slot(req)
                    self._finish_request(req, FinishReason.CANCELLED)
                    break
                tok = int(toks_T[i, t])
                # row t wrote inputs[t]'s KV at this position — the
                # bookkeeping of exactly one decode step
                if req.seq is not None:
                    req.seq.append(int(inputs[t]))
                    req.registered_blocks = \
                        self.kv_manager.register_full_blocks(
                            req.blocks, req.seq, req.registered_blocks,
                            tenant=req.tenant or None)
                    if self.has_window_pool:
                        self.kv_manager.window_register(
                            req.win, req.seq, req.blocks,
                            len(req.seq.tokens))
                req.pos += 1
                req.key_step += 1
                req.generated += 1
                req.last_token = tok
                n_applied += 1
                self.total_decode_tokens += 1
                self.spec_emitted_tokens += 1
                if t > 0:          # reaching row t>0 accepted draft t
                    self.spec_accepted_tokens += 1
                    accepted += 1
                if next_drafts is not None:
                    req.draft = int(next_drafts[i, t])
                self._mark_first_token(req)
                self._emit(req, tok, float(lps_T[i, t]))
                if next_drafts is not None and req.pos >= capacity:
                    # the context is full (as the one-step path's harvest)
                    self._release_slot(req)
                    self._finish_request(req, FinishReason.LENGTH)
                else:
                    self._maybe_finish_after_emit(req)
                if self.slots[i] is not req:
                    break          # finished: drop the overrun rows
                if t + 1 < len(inputs) and tok != int(inputs[t + 1]):
                    break          # draft rejected: rewind-rollback
            self.spec_rewound_rows += len(inputs) - n_applied
            applied.append((i, req.rid, n_applied, accepted))
        if self.recorder is not None and pending.get("id") is not None:
            self.recorder.rec("spec_harvest", id=pending["id"],
                              toks=toks_T.copy(), applied=applied)
        emitted = sum(n for _i, _r, n, _a in applied)
        accepted = sum(a for _i, _r, _n, a in applied)
        if next_drafts is None:
            self.flight.record_cycle(
                "verify", batch_fill=len(applied), spec_k=self.cfg.spec_k,
                emitted=emitted, accepted=accepted)
            return
        # a resident drafter's step IS this model's decode step: a
        # ``decode`` record with the one-step path's fields (chained: the
        # slots fed from the device behind an un-harvested step; drain:
        # why none was launched behind this one, where none was) and,
        # beside them, the rows it scored and the drafts it accepted.
        # ctx_tokens / win_tokens count each cached row ONCE a slot,
        # however many of the slot's rows read it; cache_passes says how
        # often a read fetched it for them (1 under the kernel, a pass a
        # row on the XLA gather: the serving module's answer)
        self.flight.record_cycle(
            "decode", K=1, batch_fill=len(applied),
            chained=sum(1 for i, *_ in applied if pending["mask"][i]),
            planned_tokens=len(applied), emitted=emitted, rows=rows,
            accepted=accepted, ctx_tokens=ctx_tokens, sel_tokens=ctx_tokens,
            win_tokens=win_tokens,
            cache_passes=self.model_mod.decode_cache_passes(
                self.statics, self.cfg.spec_k + 1),
            win_blocks_live=max(
                (len(r.win.held) for r in self.slots
                 if r is not None and r.win is not None), default=0),
            state_bytes=0,
            **({"drain": pending["drain"]} if "drain" in pending else {}))

    # ----------------------------------------------------------- preemption
    def _preempt_or_finish(self, req: EngineRequest) -> None:
        """KV exhaustion policy: recompute preemption (vLLM-style) when the
        pool is contended, else finish.

        The preempted request releases its blocks and goes back to the
        waiting queue with every emitted token appended to its prompt — on
        re-admission the prefill recomputes (prefix reuse recovers whatever
        survived in the pool) and the next sampled token seamlessly
        continues the client's stream. With no other active sequence,
        recompute couldn't allocate any more than the request already holds,
        so the request finishes with LENGTH instead (the pool simply is too
        small for it)."""
        others = any(s is not None and s is not req for s in self.slots)
        budget_left = req.max_new_tokens - req.generated
        in_prompt = (req.lane_prompt is not None
                     and req.pos < len(req.lane_prompt))
        emitted_len = (0 if in_prompt or req.seq is None
                       else len(req.seq.tokens) - len(req.prompt))
        new_len = len(req.prompt) + emitted_len + 1
        bs = self.cfg.kv_block_size
        fits = (new_len < self.cfg.max_model_len
                and self._blocks_needed(new_len + bs) <= self.M)
        if not others or budget_left <= 0 or not fits:
            # no contention to wait out, no budget left, or the grown
            # prompt wouldn't fit a block table on re-admission
            self._release_slot(req)
            self._finish_request(req, FinishReason.LENGTH)
            return
        self.preemptions += 1
        logger.info("preempting %s after %d tokens (KV exhausted; "
                    "recompute on re-admission)", req.rid, req.generated)
        self.flight.record("preempt", rid=req.rid,
                           generated=req.generated)
        if req.trace is not None:
            # marks the trace for tail-based retention (the collector
            # keeps full trees for preempted requests)
            req.trace.event("engine.preempted", generated=req.generated)
        if self.recorder is not None:
            self.recorder.rec("preempt", rid=req.rid,
                              generated=req.generated)
        if in_prompt:
            # lane preempted mid-prompt: nothing was emitted — requeue
            # with the original prompt unchanged (progress recomputes; no
            # recompute boundary is recorded because no sampled token
            # depended on a re-derived state)
            self._release_slot(req)
            req.key_step += len(req.lane_prompt) - req.pos - 1  # undo skew
        else:
            req.numeric_boundaries.append(req.emitted_total)
            emitted = req.seq.tokens[len(req.prompt):] if req.seq else []
            self._release_slot(req)
            req.prompt = list(req.prompt) + list(emitted) + [req.last_token]
        req.lane_prompt = None
        req.max_new_tokens = budget_left
        req.seq = None               # admission rebuilds the hash chain
        req.precomputed = None       # any shipped KV described the old prompt
        req.slot = -1
        req.pos = 0
        req.generated = 0
        req.registered_blocks = 0
        req.prefix_hit_tokens = 0
        self.waiting.put_nowait(req)
        self._work_event.set()

    # ------------------------------------------------------------- finishes
    def _mark_first_token(self, req: EngineRequest) -> None:
        """The request's first token is about to be emitted (a no-op on
        every later call, a preempted request's recompute included): stamp
        it, put the wait since its admission's dispatch returned on the
        request's trace, and write the request's one ``first_token`` flight
        record. A lane admission has no prefill dispatch: its wait covers
        the prompt's ride through the decode batches. A request submitted
        with no trace has no origin: the record holds the engine's three
        stages and leaves ``server_ms`` / ``ingest_ms`` out."""
        if req.first_token_time is not None:
            return
        now = req.first_token_time = time.monotonic()
        trace, enqueued = req.trace, req.enqueue_time
        admitted, dispatched = req.admitted_time, req.dispatched_time
        if admitted is None or dispatched is None:
            return                 # emitted by no admission path of ours
        origin = {}
        if trace is not None:
            trace.add_span("engine.first_token", dispatched, now)
            # the trace's origin (the front end's first byte; a worker's
            # child trace inherits it over the wire) to the enqueue: the
            # origin lies before this trace's own start by the two epochs
            before = trace.start_epoch - trace.origin_ts - trace.start
            origin = {"server_ms": round(1e3 * (before + now), 3),
                      "ingest_ms": round(1e3 * (before + enqueued), 3)}
        # one record a request, every field from two of its stamps: the
        # four stages tile server_ms (docs/observability.md)
        self.flight.record(
            "first_token", rid=req.rid, prompt=len(req.prompt),
            hit=req.prefix_hit_tokens, chunks=req.prefill_chunks, **origin,
            queue_wait_ms=round(1e3 * (admitted - enqueued), 3),
            prefill_ms=round(1e3 * (dispatched - admitted), 3),
            first_token_wait_ms=round(1e3 * (now - dispatched), 3))

    def _emit(self, req: EngineRequest, token: int, logprob: float) -> None:
        req.emitted_total += 1
        req.out_queue.put_nowait((token, logprob))

    def _maybe_finish_after_emit(self, req: EngineRequest) -> None:
        if req.last_token in req.eos_ids:
            self._release_slot(req)
            self._finish_request(req, FinishReason.EOS)
        elif req.generated >= req.max_new_tokens:
            self._release_slot(req)
            self._finish_request(req, FinishReason.LENGTH)
        elif req.cancelled:
            self._release_slot(req)
            self._finish_request(req, FinishReason.CANCELLED)

    def _release_slot(self, req: EngineRequest) -> None:
        if req.slot >= 0 and self.slots[req.slot] is req:
            self.slots[req.slot] = None
            self._block_tables[req.slot, :] = 0
            # reset sampler state: stale top_p/top_k would keep the
            # whole-batch `need_filter` predicate true and defeat the
            # sampler's sort-free fast path
            self._samp["temperature"][req.slot] = 0.0
            self._samp["top_k"][req.slot] = 0
            self._samp["top_p"][req.slot] = 1.0
        # write registered prefix blocks back to the host tier before the
        # device copies can be evicted; the extra hold keeps them pinned
        # until the async copy lands (released by the offload engine)
        if (self.offload_engine is not None and req.registered_blocks > 0
                and req.seq is not None):
            n = req.registered_blocks
            pinned = req.blocks[:n]
            self.kv_manager.pool.hold(pinned)
            try:
                self.offload_engine.enqueue(OffloadJob(
                    block_ids=list(pinned),
                    seq_hashes=list(req.seq.sequence_hashes[:n]),
                    tokens_hashes=list(req.seq.block_hashes[:n])))
            except Exception:
                # a failed enqueue must not strand the extra hold — the
                # pump only releases holds for jobs it actually received
                self.kv_manager.pool.release(pinned)
                raise
        if self.recorder is not None and req.blocks:
            self.recorder.rec("release", rid=req.rid,
                              blocks=list(req.blocks))
        self.kv_manager.pool.release(req.blocks)
        req.blocks = []
        self.kv_manager.window_release(req.win)

    def _finish_request(self, req: EngineRequest,
                        reason: FinishReason) -> None:
        if reason == FinishReason.CANCELLED:
            # client-stop vs deadline-budget-exhausted, counted apart
            # (nv_llm_requests_cancelled_total / _deadline_exceeded_total)
            ctx = req.ctx
            if (ctx is not None and not ctx.is_stopped
                    and getattr(ctx, "deadline_exceeded", False)):
                self.requests_deadline_exceeded_total += 1
            else:
                self.requests_cancelled_total += 1
            if req.trace is not None:
                req.trace.event("engine.cancelled",
                                generated=req.generated)
        self._inflight_reqs.pop(id(req), None)
        req.out_queue.put_nowait((_FINISH, reason))


FINISH_SENTINEL = _FINISH
