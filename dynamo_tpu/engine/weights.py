"""HF checkpoint → stacked-layer JAX params.

Reads `*.safetensors` from an HF-style model dir (the artifact the MDC's
model_path points at) and produces the stacked layout models/llama.py expects.
Torch linear weights are stored `[out, in]` → transposed to `[in, out]` for
right-multiplication; per-layer tensors are stacked on a leading L axis so
`lax.scan` consumes them directly.
"""

from __future__ import annotations

import contextlib
import glob
import os
import weakref
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

try:
    from safetensors import safe_open
    _HAVE_ST = True
except ImportError:  # pragma: no cover
    _HAVE_ST = False

_LAYER_MAP = {
    "input_layernorm.weight": ("ln1", False),
    "post_attention_layernorm.weight": ("ln2", False),
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "mlp.gate_proj.weight": ("gate", True),
    "mlp.up_proj.weight": ("up", True),
    "mlp.down_proj.weight": ("down", True),
    # qwen2-style attention biases
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    # qwen3-style per-head q/k norms
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    # mixtral MoE router
    "block_sparse_moe.gate.weight": ("router", True),
    # qwen3-moe / qwen2-moe router (same role, different HF naming; the
    # expert tensors live under mlp.experts.{e}.*_proj — _EXPERT_PREFIXES)
    "mlp.gate.weight": ("router", True),
    # qwen2_moe shared expert (dense swiglu + sigmoid gate)
    "mlp.shared_expert.gate_proj.weight": ("sh_gate", True),
    "mlp.shared_expert.up_proj.weight": ("sh_up", True),
    "mlp.shared_expert.down_proj.weight": ("sh_down", True),
    "mlp.shared_expert_gate.weight": ("sh_router", True),
    # deepseek shared experts (PLURAL naming; additive, ungated)
    "mlp.shared_experts.gate_proj.weight": ("sh_gate", True),
    "mlp.shared_experts.up_proj.weight": ("sh_up", True),
    "mlp.shared_experts.down_proj.weight": ("sh_down", True),
    # deepseek MLA attention (models/mla.py)
    "self_attn.q_a_proj.weight": ("wq_a", True),
    "self_attn.q_a_layernorm.weight": ("q_a_norm", False),
    "self_attn.q_b_proj.weight": ("wq_b", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", True),
    "self_attn.kv_a_layernorm.weight": ("kv_norm", False),
    "self_attn.kv_b_proj.weight": ("wkv_b", True),
    # deepseek_v32 lightning indexer (the model repository's names)
    "self_attn.indexer.wq_b.weight": ("idx_wq_b", True),
    "self_attn.indexer.wk.weight": ("idx_wk", True),
    "self_attn.indexer.k_norm.weight": ("idx_k_norm_w", False),
    "self_attn.indexer.k_norm.bias": ("idx_k_norm_b", False),
    "self_attn.indexer.weights_proj.weight": ("idx_w", True),
    # dots3_note's headwise attention gate (no modelling code is public:
    # the name is the gated-attention papers' g_proj, assumed)
    "self_attn.g_proj.weight": ("wg", True),
    # mimo_v2's learned sinks, one scalar a query head of a window layer (no
    # checkpoint or modelling code is on this machine: the name is assumed)
    "self_attn.attention_sink_bias": ("sink", False),
}

# the attention leaves that a model with window layers of a geometry of
# their own keeps in two stacks, by the layer's kind: latent (dots3_note)
# and grouped-query (mimo_v2)
_SWA_LEAVES = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
               "wg")
_SWA_GQA_LEAVES = ("wq", "wk", "wv", "wo", "sink", "q_norm", "k_norm")

# a resident multi-token-prediction module's own tensors under
# model.layers.{L + m}. (DeepSeek-V3's layout, which published the key
# num_nextn_predict_layers; this family's names are assumed): the two input
# norms, the projection of their concatenation, the norm before the shared
# head. Its copies of the embedding and the head are the model's and are
# not read; everything else is a decoder block's
_MTP_OWN = {"enorm.weight": ("enorm", False),
            "hnorm.weight": ("hnorm", False),
            "eh_proj.weight": ("eh_proj", True),
            "shared_head.norm.weight": ("final_norm", False)}
_MTP_SHARED = ("embed_tokens.weight", "shared_head.head.weight")


def _swa_leaves(cfg: ModelConfig) -> tuple:
    return (_SWA_LEAVES if cfg.has_swa_latent
            else _SWA_GQA_LEAVES if cfg.has_swa_gqa else ())

# mixtral expert sub-weights: w1=gate, w3=up, w2=down (all torch [out, in])
_EXPERT_MAP = {"w1": "moe_gate", "w3": "moe_up", "w2": "moe_down",
               # qwen3-moe naming for the same three matmuls
               "gate_proj": "moe_gate", "up_proj": "moe_up",
               "down_proj": "moe_down"}

# per-family expert tensor prefixes under model.layers.{i}.
_EXPERT_PREFIXES = ("block_sparse_moe.experts.", "mlp.experts.")


def _layer_map_for(cfg: ModelConfig) -> Dict[str, tuple]:
    """HF layer-tensor suffix → (stacked key, transpose) for this family.
    One home — the replicated and sharded loaders must agree."""
    layer_map = dict(_LAYER_MAP)
    if cfg.norm_on_output:
        # exaone_moe (exaone4's names): no input norms; ln1 / ln2 are the
        # norms on the attention's and the MLP's OUTPUT
        del layer_map["input_layernorm.weight"]
        layer_map["post_attention_layernorm.weight"] = ("ln1", False)
        layer_map["post_feedforward_layernorm.weight"] = ("ln2", False)
    if cfg.post_norms:
        # gemma2: "post_attention_layernorm" is a true post-attn norm (not
        # llama's pre-MLP norm) and the MLP has its own pre/post pair
        layer_map["post_attention_layernorm.weight"] = ("ln1_post", False)
        layer_map["pre_feedforward_layernorm.weight"] = ("ln2", False)
        layer_map["post_feedforward_layernorm.weight"] = ("ln2_post", False)
    if ((cfg.kv_lora_rank > 0 or cfg.has_swa_gqa)
            and cfg.num_experts > 0):
        # hybrid sparsity: mlp.*_proj exists only on the dense-prefix
        # layers and lands in the dense_* stacks (_partial_ranges)
        layer_map["mlp.gate_proj.weight"] = ("dense_gate", True)
        layer_map["mlp.up_proj.weight"] = ("dense_up", True)
        layer_map["mlp.down_proj.weight"] = ("dense_down", True)
    if cfg.moe_routing == "sigmoid_noaux":
        # deepseek_v3 router bias buffer (persistent, so it is in every
        # checkpoint's state dict)
        layer_map["mlp.gate.e_score_correction_bias"] = (
            "router_bias", False)
    if cfg.model_type == "phi3":
        # phi3 ships FUSED projections (_fused_sections); the split
        # suffixes must not also match
        for k in ("self_attn.q_proj.weight", "self_attn.k_proj.weight",
                  "self_attn.v_proj.weight", "mlp.gate_proj.weight",
                  "mlp.up_proj.weight"):
            layer_map.pop(k, None)
    return layer_map


def _fused_sections(cfg: ModelConfig) -> Dict[str, list]:
    """Fused HF layer tensors → the row sections (torch [out, in]
    orientation) that map onto our split keys: phi3 packs q/k/v into
    ``qkv_proj`` and gate/up into ``gate_up_proj`` (HF Phi3Config);
    mimo_v2's ``attention_projection_layout: fused_qkv`` is read as q | k | v
    rows at the sizes of ``cfg``'s geometry (a window layer's:
    ``cfg.swa_gqa_geometry()``; the tensor's name is assumed).
    Returns {suffix: [(key, row_offset, row_count)]}; one home for both
    loaders."""
    qd = cfg.num_heads * cfg.head_dim
    kvd = cfg.num_kv_heads * cfg.head_dim
    if cfg.has_swa_gqa:
        return {"self_attn.qkv_proj.weight": [
            ("wq", 0, qd), ("wk", qd, kvd),
            ("wv", qd + kvd, cfg.num_kv_heads * cfg.v_head_dim)]}
    if cfg.model_type != "phi3":
        return {}
    return {
        "self_attn.qkv_proj.weight": [
            ("wq", 0, qd), ("wk", qd, kvd), ("wv", qd + kvd, kvd)],
        "mlp.gate_up_proj.weight": [
            ("gate", 0, cfg.intermediate_size),
            ("up", cfg.intermediate_size, cfg.intermediate_size)],
    }


def _partial_ranges(cfg: ModelConfig):
    """Stacked keys that cover only a LAYER RANGE (deepseek hybrid
    sparsity): key -> (lo, hi) global layer bounds. Empty for uniform
    families."""
    if ((cfg.kv_lora_rank == 0 and not cfg.has_swa_gqa)
            or cfg.num_experts == 0):
        return {}
    k, L = cfg.first_k_dense, cfg.num_layers
    out = {key: (0, k) for key in ("dense_gate", "dense_up",
                                   "dense_down")}
    for key in ("router", "router_bias", "moe_gate", "moe_up",
                "moe_down", "sh_gate", "sh_up", "sh_down"):
        out[key] = (k, L)
    return out


def _layers_of_stack(cfg: ModelConfig, key: str, lo: int, hi: int) -> list:
    """The layers whose tensors the stack ``key`` holds, in order: the range
    [lo, hi), or with two attention geometries (dots3_note, mimo_v2) the
    layers of the stack's kind."""
    if not _swa_leaves(cfg) or key in ("ln1", "ln2") or lo or hi != \
            cfg.num_layers:
        return list(range(lo, hi))
    kind = "sliding_attention" if key.startswith("swa_") else \
        "full_attention"
    return [i for i, t in enumerate(cfg.layer_types) if t == kind]


def load_params_auto(model_dir: str, cfg: Optional[ModelConfig] = None,
                     mesh=None, dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """THE loader entry point: streams each device's shard straight from
    disk when a mesh is given — llama/qwen/gemma/phi3 AND MoE/MLA
    (deepseek) layouts — so host peak is one param-stack shard, never the
    full model (the enabler for 70B / deepseek-class bring-up on a
    standard TPU-VM host; the reference gets this from its engines'
    per-rank shard loaders, lib/llm vllm subprocess.rs:37-41). Without a
    mesh, the replicated reader stages the whole model in host numpy."""
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    if cfg.is_sambay:
        # phi4flash: its own names and stacks; no mesh serves it yet
        # (sambay.refusals: raised at engine build)
        return load_sambay_params(model_dir, cfg, dtype=dtype)
    if cfg.has_kda:
        # kimi_linear: its own names and fused stacks; no mesh serves it
        # yet (kimi_linear.refusals: raised at engine build)
        return load_kimi_linear_params(model_dir, cfg, dtype=dtype)
    if cfg.has_ssd:
        # granitemoehybrid: its own names, the experts' fused gate|up split;
        # no mesh serves it yet (granite_hybrid.refusals)
        return load_granite_hybrid_params(model_dir, cfg, dtype=dtype)
    if mesh is not None:
        return load_params_sharded(model_dir, mesh, cfg, dtype=dtype)
    return load_llama_params(model_dir, cfg, dtype=dtype)


class LoadAccounting:
    """Live-host-byte tracker for checkpoint loads (weakref-finalized):
    ``peak`` is the high-water mark of HEAP bytes simultaneously alive
    among the loader's STAGING copies — read-slice transients in the
    streaming path, full param-stack assemblies in the replicated path.
    The buffers the streaming loader hands to jax.make_array_from_callback
    are excluded: they become the device shard storage itself (the CPU
    backend zero-copy-aliases them), i.e. they are the model, not
    staging. Only arrays that OWN their buffer are counted: safetensors
    hands out mmap-backed views (file-cache pages the OS can evict — not
    heap), and a view's lifetime says nothing about its root buffer's
    anyway."""

    def __init__(self) -> None:
        self.live = 0
        self.peak = 0
        self.total = 0
        # largest single buffer handed to jax.make_array_from_callback —
        # the device shard storage itself (alive only until the transfer
        # completes on a real accelerator; aliased forever on CPU), kept
        # as its own number so staging and handoff cannot be conflated
        self.largest_handoff = 0

    def track(self, arr: np.ndarray) -> np.ndarray:
        if arr.base is not None:   # view — not loader-owned heap
            return arr
        nb = int(arr.nbytes)
        self.live += nb
        self.total += nb
        if self.live > self.peak:
            self.peak = self.live
        weakref.finalize(arr, self._release, nb)
        return arr

    def transient(self, nbytes: int) -> None:
        """Explicit accounting for a lexically-scoped staging buffer:
        ``nbytes`` live briefly ON TOP of the tracked live set. Used by
        the streaming read path, whose buffer lifetimes are exact
        (dead before the next read) — weakref tracking can't see them
        because safetensors slice reads surface as views of fresh
        memoryview-backed copies (measured), not as owning arrays."""
        if self.live + nbytes > self.peak:
            self.peak = self.live + nbytes
        self.total += nbytes

    def handoff(self, nbytes: int) -> None:
        if nbytes > self.largest_handoff:
            self.largest_handoff = nbytes

    def _release(self, nb: int) -> None:
        self.live -= nb


_ACCOUNTING: Optional[LoadAccounting] = None


@contextlib.contextmanager
def load_accounting():
    """``with load_accounting() as acct: load(...)`` — afterwards
    ``acct.peak``/``acct.total`` hold the staging byte counts and
    ``acct.largest_handoff`` the biggest shard buffer handed to jax, for
    every loader call made inside the block."""
    global _ACCOUNTING
    acct = LoadAccounting()
    prev = _ACCOUNTING
    _ACCOUNTING = acct
    try:
        yield acct
    finally:
        _ACCOUNTING = prev


def _track(arr: np.ndarray) -> np.ndarray:
    if _ACCOUNTING is not None:
        _ACCOUNTING.track(arr)
    return arr


def _note_handoff(arr: np.ndarray) -> np.ndarray:
    if _ACCOUNTING is not None:
        _ACCOUNTING.handoff(int(arr.nbytes))
    return arr


def _note_transient(nbytes: int) -> None:
    if _ACCOUNTING is not None:
        _ACCOUNTING.transient(int(nbytes))


# safetensors dtype tag -> on-disk bytes per element
_ST_ITEMSIZE = {"F64": 8, "I64": 8, "U64": 8, "F32": 4, "I32": 4,
                "U32": 4, "F16": 2, "BF16": 2, "I16": 2, "U16": 2,
                "I8": 1, "U8": 1, "BOOL": 1, "F8_E4M3": 1, "F8_E5M2": 1}


def _iter_safetensors(model_dir: str):
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {model_dir}")
    for path in files:
        with safe_open(path, framework="np") as f:
            for name in f.keys():
                yield name, _track(f.get_tensor(name))


def load_llama_params(model_dir: str, cfg: Optional[ModelConfig] = None,
                      dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Load an HF llama/qwen-style checkpoint into the stacked param pytree."""
    if not _HAVE_ST:
        raise RuntimeError("safetensors not available")
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    L, E = cfg.num_layers, cfg.num_experts
    layer_map = _layer_map_for(cfg)
    fused = _fused_sections(cfg)
    swa_leaves = _swa_leaves(cfg)
    fused_swa = (_fused_sections(cfg.swa_gqa_geometry())
                 if cfg.has_swa_gqa else fused)
    staging: Dict[str, list] = {}
    expert_staging: Dict[str, list] = {}   # key → [L][E] tensors
    singles: Dict[str, np.ndarray] = {}
    mtp: Dict[str, np.ndarray] = {}        # a resident module's leaves
    mtp_experts: Dict[str, list] = {}      # key → [E] tensors
    for name, tensor in _iter_safetensors(model_dir):
        if name == "model.embed_tokens.weight":
            singles["embed"] = tensor
        elif name == "model.norm.weight":
            singles["final_norm"] = tensor
        elif name == "lm_head.weight":
            singles["lm_head"] = tensor.T
        elif name.startswith("model.layers."):
            rest = name[len("model.layers."):]
            idx_str, sub = rest.split(".", 1)
            if L <= int(idx_str) < L + cfg.mtp_layers:
                # exaone_moe: the module is part of the served model
                # (models/mimo.py mtp_shapes: a stack of one layer)
                _stage_mtp(cfg, sub, tensor, layer_map, mtp, mtp_experts)
                continue
            if int(idx_str) >= L:
                if int(idx_str) < L + cfg.num_nextn_predict_layers:
                    # deepseek_v3 MTP heads live at model.layers.{L}+ —
                    # generation never runs them (HF skips them too);
                    # their attention-shaped names must not land in the
                    # decoder stacks. The bound keeps the mismatch
                    # guard: only the declared MTP indices skip
                    continue
                raise ValueError(
                    f"checkpoint tensor {name} is beyond the config's "
                    f"{L} layers (+{cfg.num_nextn_predict_layers} MTP) "
                    f"— config.json/checkpoint mismatch")
            expert_prefix = next(
                (p for p in _EXPERT_PREFIXES if sub.startswith(p)), None)
            if expert_prefix is not None:
                # {prefix}{e}.w{1,2,3}.weight (mixtral) or
                # {prefix}{e}.{gate,up,down}_proj.weight (qwen3-moe)
                e_str, wname, _ = sub[len(expert_prefix):].split(".", 2)
                key = _EXPERT_MAP.get(wname)
                if key is None:
                    continue
                # one chip's share (ModelConfig.num_experts_total): the
                # checkpoint names all the published experts; keep those
                # held here under their local index
                e_local = int(e_str) - cfg.expert_share_index * E
                if cfg.num_experts_total and not 0 <= e_local < E:
                    continue
                grid = expert_staging.setdefault(
                    key, [[None] * E for _ in range(L)])
                grid[int(idx_str)][e_local] = tensor.T
                continue
            # a window layer's attention leaves go to that kind's own stack
            swa = "swa_" if swa_leaves and cfg.layer_types[
                int(idx_str)] == "sliding_attention" else ""
            if sub in fused:
                # split the fused tensor's torch rows into our keys
                for key, off, cnt in (fused_swa if swa else fused)[sub]:
                    staging.setdefault(swa + key, [None] * L)[
                        int(idx_str)] = tensor[off:off + cnt].T
                continue
            mapped = layer_map.get(sub)
            if mapped is None:
                continue  # rotary inv_freq buffers etc.
            key, transpose = mapped
            if key in swa_leaves:
                key = swa + key
            arr = tensor.T if transpose else tensor
            staging.setdefault(key, [None] * L)[int(idx_str)] = arr

    params: Dict[str, jax.Array] = {}
    partial = _partial_ranges(cfg)
    for key, arr in singles.items():
        params[key] = jnp.asarray(arr, dtype=dtype)
    for key, per_layer in staging.items():
        lo, hi = partial.get(key, (0, L))
        want = _layers_of_stack(cfg, key, lo, hi)
        rows = [per_layer[i] for i in want]
        missing = [i for i in want if per_layer[i] is None]
        extra = [i for i, a in enumerate(per_layer) if a is not None
                 and i not in want]
        if missing or extra:
            raise ValueError(
                f"checkpoint layer coverage wrong for {key}: missing "
                f"{missing[:4]}, outside-range {extra[:4]} "
                f"(expected layers [{lo}, {hi}))")
        params[f"layers.{key}"] = jnp.asarray(
            _track(np.stack(rows, axis=0)),
            # mimo_v2's sinks stay float32 whatever the load dtype
            dtype=jnp.float32 if key == "swa_sink" else dtype)
    for key, grid in expert_staging.items():
        lo, hi = partial.get(key, (0, L))
        rows = grid[lo:hi]
        missing = [(lo + i, j) for i, row in enumerate(rows)
                   for j, a in enumerate(row) if a is None]
        extra = [(i, j) for i, row in enumerate(grid)
                 for j, a in enumerate(row)
                 if a is not None and not (lo <= i < hi)]
        if extra:
            raise ValueError(
                f"checkpoint expert coverage wrong for {key}: tensors "
                f"at layers outside [{lo}, {hi}): {extra[:4]}")
        if missing:
            raise ValueError(f"checkpoint missing experts {missing[:4]}… "
                             f"for {key}")
        params[f"layers.{key}"] = jnp.asarray(
            _track(np.stack([_track(np.stack(row, axis=0))
                             for row in rows], axis=0)),
            dtype=dtype)
    for key, arr in mtp.items():
        params[f"mtp.{key}"] = jnp.asarray(arr[None], dtype=dtype)
    for key, row in mtp_experts.items():
        if any(a is None for a in row):
            raise ValueError(f"checkpoint missing experts of the multi-"
                             f"token-prediction module for {key}")
        params[f"mtp.{key}"] = jnp.asarray(
            _track(np.stack(row, axis=0))[None], dtype=dtype)
    if "lm_head" not in params and not cfg.tie_word_embeddings:
        # some checkpoints tie implicitly by omitting lm_head
        cfg.tie_word_embeddings = True
    return params


def _stage_mtp(cfg: ModelConfig, sub: str, tensor, layer_map: dict,
               mtp: dict, mtp_experts: dict) -> None:
    """One tensor of the resident multi-token-prediction module (``sub``:
    its name under model.layers.{L}.) into ``mtp`` / ``mtp_experts``."""
    E = cfg.num_experts
    prefix = next((p for p in _EXPERT_PREFIXES if sub.startswith(p)), None)
    if prefix is not None:
        e_str, wname, _ = sub[len(prefix):].split(".", 2)
        key = _EXPERT_MAP.get(wname)
        e_local = int(e_str) - cfg.expert_share_index * E
        if key is not None and (not cfg.num_experts_total
                                or 0 <= e_local < E):
            mtp_experts.setdefault(key, [None] * E)[e_local] = tensor.T
        return
    mapped = _MTP_OWN.get(sub) or (
        None if sub in _MTP_SHARED else layer_map.get(sub))
    if mapped is not None:
        key, transpose = mapped
        # a dense_* name is the main model's leading layer's: the block's
        # MLP is read as an expert layer (assumed; models/mimo.py)
        mtp[key] = tensor.T if transpose else tensor


def load_params_sharded(model_dir: str, mesh,
                        cfg: Optional[ModelConfig] = None,
                        dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Load a checkpoint DIRECTLY into its mesh-sharded device layout.

    The replicated loader (load_llama_params) stages the whole model in
    host numpy — ~140 GB of host RAM for a 70B bf16 checkpoint, and each
    device then holds a full copy until shard_params re-places it. This
    loader reads only each device's shard from disk (safetensors
    `get_slice` reads sub-ranges without materializing the tensor) and
    assembles sharded jax Arrays with `make_array_from_callback`, so peak
    host memory is ONE param-stack shard — the practical enabler for
    70B TP-8 and deepseek-class bring-up on a standard TPU-VM host
    (BASELINE config 4; the reference gets this from its external
    engines' per-rank shard loaders, vllm subprocess.rs:37-41).

    Covers every family the engine serves: stacked dense layers
    (llama/qwen/gemma, phi3 fused tensors), MoE expert grids (mixtral /
    qwen-moe / deepseek hybrid with partial layer ranges), and MLA
    latent projections. ``load_accounting()`` wraps a load to measure
    the staging high-water mark.
    """
    if not _HAVE_ST:
        raise RuntimeError("safetensors not available")
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.sharding import fit_or_replicate, param_pspecs
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    L = cfg.num_layers

    # index pass: tensor name → OPEN file handle (headers parsed once —
    # a 70B TP-8 load issues thousands of slice reads)
    files = sorted(glob.glob(os.path.join(model_dir, "*.safetensors")))
    if not files:
        raise FileNotFoundError(f"no .safetensors under {model_dir}")
    with contextlib.ExitStack() as stack:
        handles = {path: stack.enter_context(
            safe_open(path, framework="np")) for path in files}
        where: Dict[str, object] = {}
        for f in handles.values():
            for name in f.keys():
                where[name] = f

        # "wq" → [(hf_suffix, T?), ...]: some keys have per-family HF
        # namings (router: mixtral block_sparse_moe.gate vs qwen3-moe /
        # deepseek mlp.gate) — resolve by whichever name the checkpoint
        # contains at the key's FIRST covered layer (partial-range keys
        # like the deepseek router never exist at layer 0)
        by_key: Dict[str, list] = {}
        for suffix, (key, transpose) in _layer_map_for(cfg).items():
            by_key.setdefault(key, []).append((suffix, transpose, None))
        for suffix, sections in _fused_sections(cfg).items():
            # fused tensors (phi3 qkv_proj / gate_up_proj): each split
            # key reads a torch-row window of the fused tensor — the
            # slice reader shifts AND CLAMPS the logical out-axis into
            # the section (col_off=None means unfused; 0 is a real fused
            # offset whose open slices must still clamp to the section)
            for key, off, _cnt in sections:
                by_key.setdefault(key, []).append((suffix, True, off))
        singles = {"embed": ("model.embed_tokens.weight", False),
                   "final_norm": ("model.norm.weight", False),
                   "lm_head": ("lm_head.weight", True)}
        partial = _partial_ranges(cfg)

        def read_slice(name: str, idx, transpose: bool,
                       col_off=None, col_dim: int = 0) -> np.ndarray:
            """Read tensor[idx] from disk; idx indexes the LOGICAL
            (already transposed) orientation, so transposed reads swap
            the slices. ``col_off`` (None = unfused) shifts the logical
            out-axis into a fused tensor's section and CLAMPS open
            slices to the section width ``col_dim`` — an offset of 0 is
            a real fused section whose slice(None) would otherwise read
            the whole fused axis."""
            sl = where[name].get_slice(name)
            if transpose:
                if len(idx) == 2:
                    c = idx[1]
                    if col_off is not None:
                        start, stop, step = c.indices(col_dim)
                        c = slice(start + col_off, stop + col_off, step)
                    out = np.ascontiguousarray(sl[c, idx[0]].T)
                    # the fresh slice copy and its contiguous transpose
                    # copy coexist inside this call (measured: slice
                    # reads are heap copies, not mmap views)
                    _note_transient(2 * out.nbytes)
                    return out
                out = np.ascontiguousarray(sl[idx[0]].T)
                _note_transient(2 * out.nbytes)
                return out
            out = np.ascontiguousarray(sl[tuple(idx)])
            _note_transient(out.nbytes)
            return out

        def _resolve_expert_naming(lo: int):
            """(prefix, {stacked key → hf wname}) by checkpoint presence:
            mixtral block_sparse_moe.experts.{e}.w{1,3,2} vs qwen-moe /
            deepseek mlp.experts.{e}.{gate,up,down}_proj."""
            for prefix in _EXPERT_PREFIXES:
                for wname, key in _EXPERT_MAP.items():
                    if (f"model.layers.{lo}.{prefix}0.{wname}.weight"
                            in where):
                        inv = {k: w for w, k in _EXPERT_MAP.items()
                               if (f"model.layers.{lo}.{prefix}0."
                                   f"{w}.weight") in where}
                        return prefix, inv
            raise ValueError(
                f"no expert tensors found at layer {lo} under any of "
                f"{_EXPERT_PREFIXES} — checkpoint/config mismatch")

        if "pp" in mesh.axis_names and mesh.shape["pp"] > 1:
            # pipeline-parallel mesh: layer stacks stream straight into
            # their L-over-"pp" (×in-stage "tp") placement — each rank
            # reads only ITS layer slice off disk, the per-host working
            # set the cross-host capacity axis exists for
            from ..parallel.pipeline_parallel import pp_param_pspecs
            specs = pp_param_pspecs(cfg, tp=mesh.shape["tp"])
        else:
            specs = param_pspecs(cfg)
        params: Dict[str, jax.Array] = {}
        from .models import module_for
        expert_naming = None
        for pkey, shape in module_for(cfg).param_shapes(cfg).items():
            spec = fit_or_replicate(pkey, shape, specs.get(pkey, P()),
                                    mesh, _np_dtype(dtype).itemsize)
            sharding = NamedSharding(mesh, spec)
            if pkey in singles:
                name, transpose = singles[pkey]
                if name not in where:
                    continue        # tied checkpoints omit lm_head

                def cb(idx, name=name, transpose=transpose, shape=shape):
                    # preallocate the handoff buffer and fill it in
                    # row-CHUNKS read straight off disk, so the staging
                    # transient is one chunk in the DISK dtype — not the
                    # whole (possibly f32) shard (a 70B embed shard read
                    # whole would stage GBs)
                    dims = [len(range(*sl.indices(dim)))
                            for sl, dim in zip(idx, shape)]
                    out = _note_handoff(
                        np.empty(dims, _np_dtype(dtype)))
                    r_sl = idx[0]
                    start, stop, step = r_sl.indices(shape[0])
                    disk_item = _ST_ITEMSIZE.get(
                        where[name].get_slice(name).get_dtype(), 4)
                    row_bytes = max(
                        np.prod(dims[1:], dtype=np.int64), 1) * disk_item
                    chunk = max(int((64 << 20) // row_bytes), 1)
                    for c0 in range(start, stop, chunk * step):
                        c1 = min(c0 + chunk * step, stop)
                        out[(c0 - start) // step:
                            (c1 - start) // step] = read_slice(
                            name, (slice(c0, c1, step),) + tuple(idx[1:]),
                            transpose)
                    return out

                params[pkey] = jax.make_array_from_callback(
                    shape, sharding, cb)
                continue
            key = pkey[7:] if pkey.startswith("layers.") else pkey
            lo, hi = partial.get(key, (0, L))
            Lr = hi - lo
            if key in ("moe_gate", "moe_up", "moe_down"):
                # expert grid [Lr, E, in, out]: one disk tensor per
                # (layer, expert) — each device reads ONLY its ep × tp
                # sub-grid
                if expert_naming is None:
                    expert_naming = _resolve_expert_naming(lo)
                prefix, inv = expert_naming
                if key not in inv:
                    raise ValueError(
                        f"expert projection for {pkey} not found at layer "
                        f"{lo} under model.layers.{lo}.{prefix}0.* — "
                        f"present: {sorted(inv.values())}; the checkpoint "
                        f"is missing or misnames this projection")
                wname = inv[key]
                E = shape[1]
                names = [[(f"model.layers.{lo + i}.{prefix}{e}."
                           f"{wname}.weight") for e in range(E)]
                         for i in range(Lr)]
                missing = [n for row in names for n in row
                           if n not in where]
                if missing:
                    raise ValueError(
                        f"checkpoint missing expert tensors for {pkey}: "
                        f"{missing[:3]}…")

                def cb(idx, names=names, E=E, Lr=Lr, shape=shape):
                    # preallocate the handoff buffer, fill one
                    # (layer, expert) piece at a time: the staging
                    # transient is ONE disk-dtype piece (assignment
                    # casts in place), never a stacked copy
                    l_sl, e_sl = idx[0], idx[1]
                    rest = tuple(idx[2:])
                    ls = list(range(*l_sl.indices(Lr)))
                    es = list(range(*e_sl.indices(E)))
                    dims = [len(range(*sl.indices(dim)))
                            for sl, dim in zip(rest, shape[2:])]
                    out = _note_handoff(np.empty(
                        [len(ls), len(es)] + dims, _np_dtype(dtype)))
                    for j, i in enumerate(ls):
                        for m, e in enumerate(es):
                            out[j, m] = read_slice(names[i][e], rest, True)
                    return out

                params[pkey] = jax.make_array_from_callback(
                    shape, sharding, cb)
                continue
            if key in by_key:
                cands = by_key[key]
                suffix, transpose, col_off = next(
                    (c for c in cands
                     if f"model.layers.{lo}.{c[0]}" in where), cands[0])
                names = [f"model.layers.{lo + i}.{suffix}"
                         for i in range(Lr)]
                if any(n not in where for n in names):
                    missing = [lo + i for i, n in enumerate(names)
                               if n not in where]
                    raise ValueError(
                        f"checkpoint missing layers {missing[:4]}… "
                        f"for {pkey}")
                col_dim = shape[-1]

                def cb(idx, names=names, transpose=transpose,
                       col_off=col_off, col_dim=col_dim, Lr=Lr,
                       shape=shape):
                    # prealloc-and-fill (see expert path): transient =
                    # one layer's disk-dtype slice
                    l_sl = idx[0]
                    rest = tuple(idx[1:])
                    ls = list(range(*l_sl.indices(Lr)))
                    dims = [len(range(*sl.indices(dim)))
                            for sl, dim in zip(rest, shape[1:])]
                    out = _note_handoff(np.empty(
                        [len(ls)] + dims, _np_dtype(dtype)))
                    for j, i in enumerate(ls):
                        out[j] = read_slice(
                            names[i], rest, transpose, col_off, col_dim)
                    return out

                params[pkey] = jax.make_array_from_callback(
                    shape, sharding, cb)
                continue
            raise NotImplementedError(
                f"sharded loading not implemented for {pkey}")

    if "lm_head" not in params and not cfg.tie_word_embeddings:
        cfg.tie_word_embeddings = True
    return params


# Backwards-compatible name (pre-round-5 the streaming loader was
# llama-family-only; it now covers MoE and MLA too).
load_llama_params_sharded = load_params_sharded


# phi4flash checkpoint names (the model repository's modeling_phi4flash.py,
# from memory: no network here), per layer under ``model.layers.{i}.``: the
# mixer is ``attn`` whatever its kind. -> (leaf of engine/models/sambay.py's
# ``layers.<kind>.<leaf>`` stacks, how the torch tensor becomes ours)
_T = "transpose"          # torch Linear [out, in] -> [in, out]
_SAMBAY_BLOCK = {
    "input_layernorm.weight": ("ln1_w", None),
    "input_layernorm.bias": ("ln1_b", None),
    "post_attention_layernorm.weight": ("ln2_w", None),
    "post_attention_layernorm.bias": ("ln2_b", None),
    "mlp.fc1.weight": ("mlp_gateup", _T),
    "mlp.fc2.weight": ("mlp_down", _T),
}
_SAMBAY_SSM = {
    "attn.in_proj.weight": ("ssm_in", _T),
    "attn.conv1d.weight": ("conv_w", "conv"),     # [Di, 1, K] -> [K, Di]
    "attn.conv1d.bias": ("conv_b", None),
    "attn.x_proj.weight": ("ssm_x", _T),
    "attn.dt_proj.weight": ("ssm_dt", _T),
    "attn.dt_proj.bias": ("dt_b", None),
    "attn.A_log": ("A_log", _T),                  # [Di, N] -> [N, Di]
    "attn.D": ("D", None),
    "attn.out_proj.weight": ("ssm_out", _T),
}
_SAMBAY_DIFF = {
    "attn.out_proj.weight": ("attn_out", _T),
    "attn.out_proj.bias": ("attn_out_b", None),
    "attn.inner_cross_attn.subln.weight": ("subnorm", None),
}
_SAMBAY_LAMBDAS = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
_SAMBAY_MIXER = {
    "mamba": _SAMBAY_SSM, "export": _SAMBAY_SSM,
    "window": {"attn.Wqkv.weight": ("attn_qkv", _T),
               "attn.Wqkv.bias": ("attn_qkv_b", None), **_SAMBAY_DIFF},
    "gmu": {"attn.in_proj.weight": ("gmu_in", _T),
            "attn.out_proj.weight": ("gmu_out", _T)},
    "cross": {"attn.Wqkv.weight": ("cross_q", _T),
              "attn.Wqkv.bias": ("cross_q_b", None), **_SAMBAY_DIFF},
}
_SAMBAY_MIXER["full"] = _SAMBAY_MIXER["window"]
_SAMBAY_TOP = {"model.embed_tokens.weight": "embed",
               "model.final_layernorm.weight": "final_norm",
               "model.final_layernorm.bias": "final_norm_b"}
_SAMBAY_FLOAT32 = ("A_log", "D", "dt_b", "lam")


def _sambay_tensor_names(cfg: ModelConfig) -> Dict[str, tuple]:
    """checkpoint tensor name -> (engine parameter, index in its stack,
    transform, row of ``lam``)."""
    from .models.sambay import layer_kinds
    names: Dict[str, tuple] = {k: (v, None, None, None)
                               for k, v in _SAMBAY_TOP.items()}
    seen: Dict[str, int] = {}
    for l, kind in enumerate(layer_kinds(cfg)):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        for sub, (leaf, how) in {**_SAMBAY_BLOCK,
                                 **_SAMBAY_MIXER[kind]}.items():
            names[f"model.layers.{l}.{sub}"] = (
                f"layers.{kind}.{leaf}", i, how, None)
        if kind in ("window", "full", "cross"):
            for row, lam in enumerate(_SAMBAY_LAMBDAS):
                names[f"model.layers.{l}.attn.inner_cross_attn.{lam}"] = (
                    f"layers.{kind}.lam", i, None, row)
    return names


def load_sambay_params(model_dir: str, cfg: Optional[ModelConfig] = None,
                       dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Load a phi4flash checkpoint into ``models/sambay.py``'s stacks. A
    tensor this map does not know, or a parameter the checkpoint lacks,
    fails loudly: the names are from memory."""
    from .models.sambay import param_shapes
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    shapes = param_shapes(cfg)
    names = _sambay_tensor_names(cfg)
    out = {name: np.zeros(shape, _np_dtype(
        jnp.float32 if name.rsplit(".", 1)[-1] in _SAMBAY_FLOAT32
        else dtype)) for name, shape in shapes.items()}
    missing = {(name, i, row) for name, i, _, row in names.values()}
    for tname, tensor in _iter_safetensors(model_dir):
        if tname == "lm_head.weight" and cfg.tie_word_embeddings:
            continue
        if tname not in names:
            raise ValueError(f"phi4flash checkpoint tensor {tname!r} has no "
                             f"place in engine/models/sambay.py's parameters")
        name, i, how, row = names[tname]
        t = np.asarray(tensor, np.float32)
        if how == _T:
            t = t.T
        elif how == "conv":
            t = t[:, 0, :].T
        target = out[name] if i is None else out[name][i]
        if row is not None:
            target = target[row]
        if target.shape != t.shape:
            raise ValueError(f"{tname}: shape {t.shape}, the engine holds "
                             f"{target.shape} for {name}")
        target[...] = t
        missing.discard((name, i, row))
    if missing:
        raise ValueError(f"phi4flash checkpoint lacks {len(missing)} "
                         f"tensor(s), e.g. {sorted(map(str, missing))[:3]}")
    return {name: jnp.asarray(_note_handoff(a)) for name, a in out.items()}


def save_sambay_hf_style(params: Dict[str, jax.Array], cfg: ModelConfig,
                         out_dir: str) -> None:
    """The inverse of ``load_sambay_params`` (tests)."""
    from safetensors.numpy import save_file
    os.makedirs(out_dir, exist_ok=True)
    out = {}
    for tname, (name, i, how, row) in _sambay_tensor_names(cfg).items():
        t = np.asarray(params[name], np.float32)
        t = t if i is None else t[i]
        t = t if row is None else t[row]
        if how == _T:
            t = t.T
        elif how == "conv":
            t = t.T[:, None, :]
        out[tname] = np.ascontiguousarray(t)
    save_file(out, os.path.join(out_dir, "model.safetensors"))


# kimi_linear checkpoint names (the model repository's modelling code, from
# memory: no network here), per layer under ``model.layers.{i}.``. -> (leaf
# of engine/models/kimi_linear.py's stacks, how the torch tensor becomes
# ours, the columns of a fused leaf it fills: a part's index among the
# parts of ``_KIMI_FUSED``)
_KIMI_KDA = {
    "self_attn.q_proj.weight": ("kda_in", _T, 0),
    "self_attn.k_proj.weight": ("kda_in", _T, 1),
    "self_attn.v_proj.weight": ("kda_in", _T, 2),
    "self_attn.q_conv1d.weight": ("kda_conv", "conv", 0),  # [P, 1, K]
    "self_attn.k_conv1d.weight": ("kda_conv", "conv", 1),
    "self_attn.v_conv1d.weight": ("kda_conv", "conv", 2),
    "self_attn.f_a_proj.weight": ("kda_low", _T, 0),
    "self_attn.g_a_proj.weight": ("kda_low", _T, 1),
    "self_attn.b_proj.weight": ("kda_low", _T, 2),
    "self_attn.f_b_proj.weight": ("kda_fb", _T, None),
    "self_attn.g_b_proj.weight": ("kda_gb", _T, None),
    "self_attn.g_b_proj.bias": ("kda_gb_bias", None, None),
    "self_attn.A_log": ("kda_A_log", "flat", None),
    "self_attn.dt_bias": ("kda_dt_bias", None, None),
    "self_attn.o_norm.weight": ("kda_onorm", None, None),
    "self_attn.o_proj.weight": ("kda_wo", _T, None),
}
_KIMI_MLA = {
    "self_attn.q_proj.weight": ("wq", _T, None),
    "self_attn.kv_a_proj_with_mqa.weight": ("wkv_a", _T, None),
    "self_attn.kv_a_layernorm.weight": ("kv_norm", None, None),
    "self_attn.kv_b_proj.weight": ("wkv_b", _T, None),
    "self_attn.o_proj.weight": ("wo", _T, None),
}
_KIMI_NORMS = {"input_layernorm.weight": "ln1",
               "post_attention_layernorm.weight": "ln2"}
_KIMI_FLOAT32 = ("kda_A_log", "kda_dt_bias", "kda_conv")


def _kimi_fused_parts(cfg: ModelConfig) -> Dict[str, tuple]:
    """The widths of a fused leaf's parts, in order."""
    P = cfg.kda_num_heads * cfg.kda_head_dim
    return {"kda_in": (P, P, P), "kda_conv": (P, P, P),
            "kda_low": (cfg.kda_head_dim, cfg.kda_head_dim,
                        cfg.kda_num_heads)}


def _kimi_tensor_names(cfg: ModelConfig) -> Dict[str, tuple]:
    """checkpoint tensor name -> (engine parameter, index in its stack
    (a tuple: layer, expert), transform, (first column, width) of a fused
    leaf or None). Experts this chip does not hold and vocabulary rows
    beyond its slice have no entry: ``load_kimi_linear_params`` passes
    them over by ``_kimi_elsewhere``."""
    from .models.mla import layer_kinds
    names: Dict[str, tuple] = {
        "model.embed_tokens.weight": ("embed", (), "rows", None),
        "model.norm.weight": ("final_norm", (), None, None),
        "lm_head.weight": ("lm_head", (), "head", None)}
    parts = _kimi_fused_parts(cfg)
    seen: Dict[str, int] = {}
    k = cfg.first_k_dense
    first = cfg.expert_share_index * cfg.num_experts
    for l, kind in enumerate(layer_kinds(cfg)):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        pre = f"model.layers.{l}."
        for sub, leaf in _KIMI_NORMS.items():
            names[pre + sub] = (f"layers.{leaf}", (l,), None, None)
        for sub, (leaf, how, part) in (_KIMI_KDA if kind == "K"
                                       else _KIMI_MLA).items():
            cols = None
            if part is not None:
                widths = parts[leaf]
                cols = (sum(widths[:part]), widths[part])
            names[pre + sub] = (f"layers.{leaf}", (i,), how, cols)
        if l < k:
            for sub, leaf in (("gate_proj", "dense_gate"),
                              ("up_proj", "dense_up"),
                              ("down_proj", "dense_down")):
                names[f"{pre}mlp.{sub}.weight"] = (
                    f"layers.{leaf}", (l,), _T, None)
            continue
        m = l - k
        moe = pre + "block_sparse_moe."
        names[moe + "gate.weight"] = ("layers.router", (m,), _T, None)
        names[moe + "gate.e_score_correction_bias"] = (
            "layers.router_bias", (m,), None, None)
        for e in range(cfg.num_experts):
            for sub, leaf in (("w1", "moe_gate"), ("w3", "moe_up"),
                              ("w2", "moe_down")):
                names[f"{moe}experts.{first + e}.{sub}.weight"] = (
                    f"layers.{leaf}", (m, e), _T, None)
        for sub, leaf in (("gate_proj", "sh_gate"), ("up_proj", "sh_up"),
                          ("down_proj", "sh_down")):
            names[f"{moe}shared_experts.{sub}.weight"] = (
                f"layers.{leaf}", (m,), _T, None)
    return names


def _kimi_elsewhere(cfg: ModelConfig, tname: str) -> bool:
    """A routed expert's tensor that another chip of the share holds."""
    import re
    hit = re.search(r"block_sparse_moe\.experts\.(\d+)\.", tname)
    if not hit or not cfg.num_experts_total:
        return False
    e = int(hit.group(1))
    first = cfg.expert_share_index * cfg.num_experts
    return (0 <= e < cfg.num_experts_total
            and not first <= e < first + cfg.num_experts)


def load_kimi_linear_params(model_dir: str,
                            cfg: Optional[ModelConfig] = None,
                            dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Load a kimi_linear checkpoint into ``models/kimi_linear.py``'s
    stacks: the q|k|v projections and convolutions and the low-rank first
    halves into their fused leaves' columns, this chip's share of the
    experts (the others' tensors are passed over) and its slice of the
    vocabulary (the leading rows). A tensor this map does not know, or a
    parameter the checkpoint lacks, fails loudly: the names are from
    memory."""
    from .models.kimi_linear import param_shapes
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    shapes = param_shapes(cfg)
    names = _kimi_tensor_names(cfg)
    out = {name: np.zeros(shape, _np_dtype(
        jnp.float32 if name.rsplit(".", 1)[-1] in _KIMI_FLOAT32
        else dtype)) for name, shape in shapes.items()}
    missing = set(names)
    for tname, tensor in _iter_safetensors(model_dir):
        if tname not in names:
            if _kimi_elsewhere(cfg, tname):
                continue
            raise ValueError(f"kimi_linear checkpoint tensor {tname!r} has "
                             f"no place in engine/models/kimi_linear.py's "
                             f"parameters")
        name, idx, how, cols = names[tname]
        t = np.asarray(tensor, np.float32)
        if how == _T:
            t = t.T
        elif how == "conv":
            t = t[:, 0, :].T
        elif how == "flat":
            t = t.reshape(-1)
        elif how == "rows":
            t = t[:cfg.vocab_size]
        elif how == "head":
            t = t[:cfg.vocab_size].T
        target = out[name][idx] if idx else out[name]
        if cols is not None:
            target = target[..., cols[0]:cols[0] + cols[1]]
        if target.shape != t.shape:
            raise ValueError(f"{tname}: shape {t.shape}, the engine holds "
                             f"{target.shape} for {name}")
        target[...] = t
        missing.discard(tname)
    if missing:
        raise ValueError(f"kimi_linear checkpoint lacks {len(missing)} "
                         f"tensor(s), e.g. {sorted(missing)[:3]}")
    return {name: jnp.asarray(_note_handoff(a)) for name, a in out.items()}


def save_kimi_linear_hf_style(params: Dict[str, jax.Array],
                              cfg: ModelConfig, out_dir: str) -> None:
    """The inverse of ``load_kimi_linear_params`` (tests): the held share
    and slice under the checkpoint's names."""
    from safetensors.numpy import save_file
    os.makedirs(out_dir, exist_ok=True)
    out = {}
    for tname, (name, idx, how, cols) in _kimi_tensor_names(cfg).items():
        t = np.asarray(params[name], np.float32)
        t = t[idx] if idx else t
        if cols is not None:
            t = t[..., cols[0]:cols[0] + cols[1]]
        if how in (_T, "head"):
            t = t.T
        elif how == "conv":
            t = t.T[:, None, :]
        elif how == "flat":
            t = t.reshape(1, 1, -1, 1)
        out[tname] = np.ascontiguousarray(t)
    save_file(out, os.path.join(out_dir, "model.safetensors"))


# granitemoehybrid checkpoint names (the published ``transformers`` modelling
# code, GraniteMoeHybrid*, from memory: no network here), per layer under
# ``model.layers.{i}.``. UNVERIFIED: no published checkpoint has been read
# through this map (the repository holds none; the round trip through
# ``save_granite_hybrid_hf_style`` proves the transforms, not the names). -> (leaf of engine/models/granite_hybrid.py's stacks,
# how the torch tensor becomes ours)
_GRANITE_MAMBA = {
    "mamba.in_proj.weight": ("ssd_in", _T),            # z | xBC | dt rows
    "mamba.conv1d.weight": ("ssd_conv", "conv"),       # [lanes, 1, taps]
    "mamba.conv1d.bias": ("ssd_conv_b", None),
    "mamba.dt_bias": ("ssd_dt_bias", None),
    "mamba.A_log": ("ssd_A_log", None),
    "mamba.D": ("ssd_D", None),
    "mamba.norm.weight": ("ssd_norm", None),
    "mamba.out_proj.weight": ("ssd_out", _T),
}
_GRANITE_ATTN = {
    "self_attn.q_proj.weight": ("wq", _T),
    "self_attn.k_proj.weight": ("wk", _T),
    "self_attn.v_proj.weight": ("wv", _T),
    "self_attn.o_proj.weight": ("wo", _T),
}
# every layer: the norms, the router, the experts' fused input ([E, 2F, D]:
# gate rows, then up rows) and output ([E, D, F]) and the shared expert's
# ([2Fs, D], [D, Fs])
_GRANITE_EVERY = {
    "input_layernorm.weight": ("ln1", None),
    "post_attention_layernorm.weight": ("ln2", None),
    "block_sparse_moe.router.layer.weight": ("router", _T),
    "block_sparse_moe.input_linear.weight": (("moe_gate", "moe_up"),
                                             "halves"),
    "block_sparse_moe.output_linear.weight": ("moe_down", "experts"),
    "shared_mlp.input_linear.weight": (("sh_gate", "sh_up"), "halves"),
    "shared_mlp.output_linear.weight": ("sh_down", _T),
}
_GRANITE_FLOAT32 = ("ssd_A_log", "ssd_dt_bias", "ssd_D", "ssd_conv")


def _granite_tensor_names(cfg: ModelConfig) -> Dict[str, tuple]:
    """checkpoint tensor name -> (engine leaf or the two a fused tensor
    fills, the layer's index in that stack, transform)."""
    from .models.granite_hybrid import layer_kinds
    names: Dict[str, tuple] = {
        "model.embed_tokens.weight": ("embed", None, None),
        "model.norm.weight": ("final_norm", None, None)}
    if not cfg.tie_word_embeddings:
        names["lm_head.weight"] = ("lm_head", None, _T)
    seen: Dict[str, int] = {}
    for l, kind in enumerate(layer_kinds(cfg)):
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        pre = f"model.layers.{l}."
        for sub, (leaf, how) in _GRANITE_EVERY.items():
            names[pre + sub] = (leaf, l, how)
        for sub, (leaf, how) in (_GRANITE_MAMBA if kind == "M"
                                 else _GRANITE_ATTN).items():
            names[pre + sub] = (leaf, i, how)
    return names


def _granite_parts(t: np.ndarray, how) -> list:
    """A checkpoint tensor as the engine's leaves hold it: one array, or
    two for a fused gate|up."""
    if how == _T:
        return [t.T]
    if how == "conv":
        return [t[:, 0, :].T]
    if how == "experts":                     # [E, D, F] -> [E, F, D]
        return [np.swapaxes(t, -1, -2)]
    if how == "halves":                      # [.., 2F, D] -> 2 x [.., D, F]
        half = t.shape[-2] // 2
        return [np.swapaxes(t[..., :half, :], -1, -2),
                np.swapaxes(t[..., half:, :], -1, -2)]
    return [t]


def load_granite_hybrid_params(model_dir: str,
                               cfg: Optional[ModelConfig] = None,
                               dtype=jnp.bfloat16) -> Dict[str, jax.Array]:
    """Load a granitemoehybrid checkpoint into ``models/granite_hybrid.py``'s
    stacks: the Mamba-2 and attention leaves at the layer's index among its
    kind, the experts' and the shared expert's fused input split into gate
    and up. A tensor this map does not know, or a parameter the checkpoint
    lacks, fails loudly: the names are from memory. A depth cut below the
    checkpoint's passes the deeper layers' tensors over."""
    import re
    from .models.granite_hybrid import param_shapes
    cfg = cfg or ModelConfig.from_model_dir(model_dir)
    names = _granite_tensor_names(cfg)
    out = {name: np.zeros(shape, _np_dtype(
        jnp.float32 if name.rsplit(".", 1)[-1] in _GRANITE_FLOAT32
        else dtype)) for name, shape in param_shapes(cfg).items()}
    missing = set(names)
    for tname, tensor in _iter_safetensors(model_dir):
        if tname not in names:
            deeper = re.match(r"model\.layers\.(\d+)\.", tname)
            if deeper and int(deeper.group(1)) >= cfg.num_layers:
                continue
            raise ValueError(f"granitemoehybrid checkpoint tensor {tname!r} "
                             f"has no place in engine/models/"
                             f"granite_hybrid.py's parameters")
        leaf, idx, how = names[tname]
        leaves = leaf if isinstance(leaf, tuple) else (leaf,)
        for one, t in zip(leaves, _granite_parts(
                np.asarray(tensor, np.float32), how)):
            name = one if idx is None else f"layers.{one}"
            target = out[name] if idx is None else out[name][idx]
            if target.shape != t.shape:
                raise ValueError(f"{tname}: shape {t.shape}, the engine "
                                 f"holds {target.shape} for {name}")
            target[...] = t
        missing.discard(tname)
    if missing:
        raise ValueError(f"granitemoehybrid checkpoint lacks {len(missing)} "
                         f"tensor(s), e.g. {sorted(missing)[:3]}")
    return {name: jnp.asarray(_note_handoff(a)) for name, a in out.items()}


def save_granite_hybrid_hf_style(params: Dict[str, jax.Array],
                                 cfg: ModelConfig, out_dir: str) -> None:
    """The inverse of ``load_granite_hybrid_params`` (tests)."""
    from safetensors.numpy import save_file
    os.makedirs(out_dir, exist_ok=True)
    out = {}
    for tname, (leaf, idx, how) in _granite_tensor_names(cfg).items():
        def held(one):
            t = np.asarray(params[one if idx is None else f"layers.{one}"],
                           np.float32)
            return t if idx is None else t[idx]
        if how == "halves":
            t = np.concatenate([np.swapaxes(held(one), -1, -2)
                                for one in leaf], axis=-2)
        elif how in (_T, "experts"):
            t = np.swapaxes(held(leaf), -1, -2)
        elif how == "conv":
            t = held(leaf).T[:, None, :]
        else:
            t = held(leaf)
        out[tname] = np.ascontiguousarray(t)
    save_file(out, os.path.join(out_dir, "model.safetensors"))


def _np_dtype(dtype):
    name = jnp.dtype(dtype).name
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def save_hf_style(params: Dict[str, jax.Array], cfg: ModelConfig,
                  out_dir: str) -> None:
    """Write params back out as a single HF-style safetensors file (used by
    tests to cross-check against the torch reference implementation)."""
    from safetensors.numpy import save_file
    if ((cfg.kv_lora_rank > 0 or cfg.has_swa_gqa)
            and cfg.num_experts > 0):
        raise NotImplementedError(
            "save_hf_style cannot write the deepseek hybrid MoE layout "
            "(partial layer stacks + deepseek expert naming), nor "
            "mimo_v2's (attention stacks by layer kind); the MLA and "
            "mimo_v2 tests carry their own converters")
    os.makedirs(out_dir, exist_ok=True)

    def c(a) -> np.ndarray:
        # save_file serializes the raw buffer — it MUST be C-contiguous
        # (np.asarray of a jax array can surface a column-major buffer).
        return np.ascontiguousarray(np.asarray(a, np.float32))

    out: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": c(params["embed"]),
        "model.norm.weight": c(params["final_norm"]),
    }
    if "lm_head" in params:
        out["lm_head.weight"] = c(np.asarray(params["lm_head"], np.float32).T)
    inv = {v[0]: (k, v[1]) for k, v in _LAYER_MAP.items()}
    # _LAYER_MAP maps BOTH shared-expert namings (qwen2 singular,
    # deepseek plural) onto sh_*; the dict inversion keeps whichever
    # iterated last — pin the family's own naming explicitly
    if cfg.model_type == "qwen2_moe":
        inv["sh_gate"] = ("mlp.shared_expert.gate_proj.weight", True)
        inv["sh_up"] = ("mlp.shared_expert.up_proj.weight", True)
        inv["sh_down"] = ("mlp.shared_expert.down_proj.weight", True)
    if cfg.post_norms:   # gemma2 norm naming (see load_llama_params)
        inv["ln1_post"] = ("post_attention_layernorm.weight", False)
        inv["ln2"] = ("pre_feedforward_layernorm.weight", False)
        inv["ln2_post"] = ("post_feedforward_layernorm.weight", False)
    # two HF namings map to "router"/each expert matmul (mixtral vs
    # qwen3-moe); saving must pick the family's names explicitly
    if cfg.model_type in ("qwen3_moe", "qwen2_moe"):
        inv["router"] = ("mlp.gate.weight", True)
        inv_experts = {"moe_gate": "gate_proj", "moe_up": "up_proj",
                       "moe_down": "down_proj"}
        expert_prefix = "mlp.experts."
    else:
        inv["router"] = ("block_sparse_moe.gate.weight", True)
        inv_experts = {"moe_gate": "w1", "moe_up": "w3",
                       "moe_down": "w2"}
        expert_prefix = "block_sparse_moe.experts."
    fused = _fused_sections(cfg)
    for suffix, sections in fused.items():
        # phi3 fused tensors: concatenate our split keys back into the
        # HF torch-row layout (inverse of the loaders' split)
        for key, _off, _cnt in sections:
            inv.pop(key, None)
        L = cfg.num_layers
        for i in range(L):
            rows = [np.asarray(params[f"layers.{k}"][i], np.float32).T
                    for k, _o, _c in sections]
            out[f"model.layers.{i}.{suffix}"] = np.ascontiguousarray(
                np.concatenate(rows, axis=0))
    for key, (hf_sub, transpose) in inv.items():
        if f"layers.{key}" not in params:
            continue
        stacked = np.ascontiguousarray(
            np.asarray(params[f"layers.{key}"], np.float32))
        for i in range(stacked.shape[0]):
            arr = stacked[i].T if transpose else stacked[i]
            out[f"model.layers.{i}.{hf_sub}"] = np.ascontiguousarray(arr)
    for key, wname in inv_experts.items():
        if f"layers.{key}" not in params:
            continue
        stacked = np.asarray(params[f"layers.{key}"], np.float32)  # [L,E,..]
        for i in range(stacked.shape[0]):
            for e in range(stacked.shape[1]):
                out[(f"model.layers.{i}.{expert_prefix}"
                     f"{e}.{wname}.weight")] = np.ascontiguousarray(
                         stacked[i, e].T)
    save_file(out, os.path.join(out_dir, "model.safetensors"))
