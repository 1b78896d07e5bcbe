"""Model + engine configuration.

The reference delegates model config to external engines (vLLM/TRT-LLM); here
the engine is ours, so the model config is first-class. Parsed from HF-style
config.json (the same artifact the reference's ModelDeploymentCard points at,
lib/llm/src/model_card/create.rs).
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class RopeScaling:
    """Rope scaling (config.json `rope_scaling`): llama3-style fields
    plus the yarn fields deepseek checkpoints carry (models/mla.py
    rope_params)."""

    rope_type: str = "default"
    factor: float = 1.0
    low_freq_factor: float = 1.0
    high_freq_factor: float = 4.0
    original_max_position_embeddings: int = 8192
    # yarn (deepseek_v2): 0.0 = absent (HF infers attention scaling
    # from `factor` alone then). attention_factor, when set, OVERRIDES
    # the mscale inference (HF priority order).
    mscale: float = 0.0
    mscale_all_dim: float = 0.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 0.0
    # longrope (phi3 128k variants): per-dim frequency divisors, one per
    # head_dim/2 lane pair. HF switches short→long per forward when
    # seq_len exceeds original_max; a paged serving engine caches K
    # post-rope and cannot re-rope on crossing, so selection is STATIC:
    # "auto" = long iff max_position_embeddings > original_max (the
    # 128k deployment), "short" = the engine proved every servable
    # sequence fits the pretrained window (EngineCore downgrades when
    # max_model_len <= original_max — HF-exact for every request it can
    # serve). The sqrt(1 + ln(M/O)/ln(O)) attention factor multiplies
    # cos/sin in BOTH modes, exactly as HF's fixed attention_scaling.
    short_factor: tuple = ()
    long_factor: tuple = ()
    longrope_active: str = "auto"


def _rope_type(raw_rs: Dict[str, Any]) -> str:
    """Normalized rope type of a raw rope_scaling dict — THE one home
    for the key fallback ("rope_type" | legacy "type") and the
    "su"→"longrope" aliasing (early Phi-3 configs)."""
    rt = raw_rs.get("rope_type", raw_rs.get("type", "default"))
    return "longrope" if rt == "su" else rt


# model_types whose configs may carry routed experts (from_hf_config refuses
# any other that does, by name)
MOE_FAMILIES = ("mixtral", "qwen2_moe", "qwen3_moe", "deepseek_v2",
                "deepseek_v3", "deepseek_v32", "kimi_k2", "dots3_note",
                "mimo_v2", "exaone_moe", "kimi_linear", "granitemoehybrid")


@dataclasses.dataclass
class ModelConfig:
    """Transformer shape config (llama / qwen / mixtral families)."""

    model_type: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: int = 128
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    rope_scaling: Optional[RopeScaling] = None
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    # MoE (mixtral-style); num_experts == 0 → dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # routing-weight convention: True = softmax renormalized over the
    # top-k (mixtral, qwen3_moe); False = softmax over ALL experts with
    # the top-k weights used as-is (qwen2_moe norm_topk_prob=false)
    moe_norm_topk: bool = True
    # qwen2_moe shared expert: a dense swiglu MLP of this intermediate
    # size added to every token, scaled by a learned sigmoid gate
    shared_expert_size: int = 0
    # qwen3-style per-head q/k norm
    qk_norm: bool = False
    # MLA (deepseek_v2 / deepseek_v3 / deepseek_v32): latent-KV attention
    # dims for models/mla.py, parsed by from_hf_config; kv_lora_rank > 0
    # is what EngineCore dispatches on (core.is_mla). q_lora_rank 0 =
    # plain q_proj (the -Lite layout).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # deepseek MoE deltas (models/mla.py): the first k layers are DENSE
    # with their own intermediate size; routed weights scale by
    # routed_scaling; group-limited routing masks scores to the
    # topk_group best of n_group expert groups before the top-k.
    # moe_routing picks the scoring function: "softmax" (deepseek_v2
    # greedy / group_limited_greedy) or "sigmoid_noaux" (deepseek_v3
    # noaux_tc: sigmoid scores + e_score_correction_bias group choice)
    moe_routing: str = "softmax"
    # deepseek_v3 multi-token-prediction heads: checkpoints carry this
    # many EXTRA layer indices at model.layers.{num_layers}+ that
    # generation never runs — the loader skips exactly that many and
    # still fails loudly on any further excess layer
    num_nextn_predict_layers: int = 0
    # deepseek_v32 sparse attention (models/mla.py): a lightning indexer
    # of index_n_heads × index_head_dim scores every cached token per
    # query, and the main attention reads only the index_topk best.
    # 0 = no indexer: v2/v3 programs are unchanged. The index keys live
    # in a second per-token cache beside the latent pool (kv["idx"]).
    index_n_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # one chip's share of the routed experts (guide "model-configs" §4):
    # num_experts counts the experts HELD here (the stacked tensors'
    # E axis); the router keeps the published width num_experts_total
    # (0 = all are held) and this chip holds the experts
    # [expert_share_index * num_experts, +num_experts). What the absent
    # experts would add is left out; nothing stands in for them.
    num_experts_total: int = 0
    expert_share_index: int = 0
    first_k_dense: int = 0
    dense_intermediate_size: int = 0
    routed_scaling: float = 1.0
    n_group: int = 0
    topk_group: int = 0
    # gemma-family deltas (model_type gemma/gemma2): gelu MLP, scaled
    # embeddings, (1+w) RMSNorm, post-block norms, logit soft-capping
    hidden_act: str = "silu"          # silu | gelu_pytorch_tanh
    embed_scale: bool = False         # multiply embeddings by sqrt(hidden)
    norm_plus_one: bool = False       # RMSNorm uses (1 + weight)
    post_norms: bool = False          # gemma2 post-attn/post-ffw norms
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    query_pre_attn_scalar: Optional[float] = None  # None → head_dim
    # gemma2 interleaves sliding-window (local) and global attention
    # layers; which layers are local comes from HF ``layer_types`` (or the
    # even-layers-local default)
    sliding_window: Optional[int] = None
    layer_types: Optional[List[str]] = None
    # phi4flash (models/sambay.py, docs/hybrid_cache.md): the SambaY
    # decoder-hybrid-decoder. mamba_d_state > 0 is what EngineCore
    # dispatches on (ModelConfig.is_sambay). Mamba-1 layers of d_inner =
    # mamba_expand * hidden_size channels with mamba_d_state states each,
    # a causal conv of mamba_d_conv taps and a dt projection of rank
    # mamba_dt_rank; every mb_per_layer-th layer is of the state-space
    # kind, the others attend (sliding_window is the window of the first
    # half's attention layers). Norms are LayerNorm with bias
    # (rms_norm_eps holds layer_norm_eps); there is no positional
    # encoding. Layer kinds by index: sambay.layer_kinds.
    # dots3_note (models/mla.py, docs/hybrid_cache.md): a second latent-
    # attention geometry for the layers whose layer_types entry is
    # "sliding_attention" (the config's swa_* keys; swa_kv_lora_rank > 0 is
    # what says the model has them), which attend over the last swa_window
    # positions, the query's own included, and keep their rows in a pool of
    # their own (kv["win"]); a sigmoid gate a head on the attention output
    # of both kinds (attention_gate), and the two LoRA latents rescaled by
    # sqrt(hidden / rank) after their norms (mla_lora_rescale)
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 0.0
    swa_window: int = 0
    attention_gate: bool = False
    mla_lora_rescale: bool = False
    # mimo_v2 (models/mimo.py, docs/hybrid_cache.md part three): plain
    # grouped-query attention of TWO geometries. The layers whose
    # layer_types entry is "sliding_attention" have swa_num_heads query and
    # swa_num_kv_heads key/value heads (> 0 is what says the model has
    # them) of swa_head_dim / swa_v_head_dim lanes, rope base
    # swa_rope_theta, attend over the last swa_window positions (the
    # query's own included), keep their rows in a pool of their own
    # (kv["win_k"], kv["win_v"]) and, with swa_sink, add one learned scalar
    # a query head to the softmax's denominator. Both kinds: keys of
    # head_dim and values of v_head_dim lanes, rope on the first rotary_dim
    # lanes of a head (0 = all of them), values times value_scale
    swa_num_kv_heads: int = 0
    swa_head_dim: int = 0
    swa_sink: bool = False
    rotary_dim: int = 0
    value_scale: float = 1.0
    # exaone_moe (models/mimo.py at ONE head geometry for both kinds,
    # docs/hybrid_cache.md part four): a norm on each sub-layer's OUTPUT and
    # none on its input (norm_on_output: ln1 / ln2 are those), no rope in
    # the full layers (nope_full), and mtp_layers multi-token-prediction
    # modules held beside the layers (enorm, hnorm, eh_proj, one decoder
    # block of kind "F" with rows of its own in the paged group, its final
    # norm; embedding and head the model's): a drafter that is part of the
    # model (engine/core.py, docs/speculative.md). 0 = none is held
    norm_on_output: bool = False
    nope_full: bool = False
    mtp_layers: int = 0
    # kimi_linear (models/kimi_linear.py, docs/hybrid_cache.md part five):
    # layers whose layer_types entry is "linear_attention" are Kimi Delta
    # Attention (engine/kda.py): kda_num_heads heads of kda_head_dim key and
    # value lanes, a gated delta rule over a float32 [heads, dim, dim] state
    # a slot and layer, q / k / v through depthwise causal convolutions of
    # kda_conv_kernel taps (kda_num_heads > 0 is what says the model has
    # them); its "full_attention" layers are mla.py's latent block with a
    # plain q projection and, with mla_nope, no rotation of the pe lanes
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 0
    mla_nope: bool = False
    # granitemoehybrid (models/granite_hybrid.py, docs/hybrid_cache.md part
    # six): layers whose layer_types entry is "mamba" are Mamba-2
    # (engine/ssd.py): ssd_num_heads heads of ssd_head_dim lanes, one decay
    # a head and token over a float32 [heads, lanes, ssd_d_state] state a
    # slot and layer, x | B | C (one B / C group) through ONE depthwise
    # causal convolution of ssd_conv_kernel taps with a bias
    # (ssd_num_heads > 0 is what says the model has them); its "attention"
    # layers are grouped-query rows in llama.py's paged pool with no
    # rotation (nope_full) at the score scale attention_multiplier, held as
    # query_pre_attn_scalar = attention_multiplier^-2. Granite's three
    # multipliers: embedding_multiplier on the embedding rows (0: none;
    # embed_scale is gemma's sqrt(hidden)), residual_multiplier on every
    # branch before it joins the stream, logits / logits_scaling
    ssd_num_heads: int = 0
    ssd_head_dim: int = 0
    ssd_d_state: int = 0
    ssd_conv_kernel: int = 0
    embedding_multiplier: float = 0.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    mamba_d_state: int = 0
    mamba_d_conv: int = 0
    mamba_expand: int = 0
    mamba_dt_rank: int = 0
    mb_per_layer: int = 0
    # runtime switch, not model geometry: the engine clears this when the
    # head is mesh-sharded (tp>1) — the fused Pallas head has no GSPMD
    # partitioning rule (models/llama.py _lm_head_kernel_ok)
    lm_head_pallas: bool = True

    @property
    def router_width(self) -> int:
        """Experts the router scores: the published count, of which
        num_experts are held here."""
        return self.num_experts_total or self.num_experts

    @property
    def has_swa_latent(self) -> bool:
        """Window layers with a latent geometry of their own (dots3_note)."""
        return self.swa_kv_lora_rank > 0

    def swa_geometry(self) -> "ModelConfig":
        """This model as its window layers see it: the swa_* sizes under
        the names models/mla.py reads, no indexer, no yarn."""
        return dataclasses.replace(
            self, num_heads=self.swa_num_heads,
            q_lora_rank=self.swa_q_lora_rank,
            kv_lora_rank=self.swa_kv_lora_rank,
            qk_nope_head_dim=self.swa_qk_nope_head_dim,
            qk_rope_head_dim=self.swa_qk_rope_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta,
            index_n_heads=0, index_head_dim=0, index_topk=0)

    @property
    def has_swa_gqa(self) -> bool:
        """Window layers with a grouped-query geometry of their own
        (mimo_v2)."""
        return self.swa_num_kv_heads > 0

    def swa_gqa_geometry(self) -> "ModelConfig":
        """This model as its window layers see it: the swa_* sizes under
        the names the grouped-query block reads (models/mimo.py)."""
        return dataclasses.replace(
            self, num_heads=self.swa_num_heads,
            num_kv_heads=self.swa_num_kv_heads, head_dim=self.swa_head_dim,
            v_head_dim=self.swa_v_head_dim, rope_theta=self.swa_rope_theta)

    @property
    def is_sambay(self) -> bool:
        """State-space, window and shared-cache layers in one model
        (phi4flash): models/sambay.py serves it."""
        return self.mamba_d_state > 0

    @property
    def has_kda(self) -> bool:
        """Kimi-Delta-Attention layers beside latent-attention ones
        (kimi_linear): models/kimi_linear.py serves it."""
        return self.kda_num_heads > 0

    @property
    def has_ssd(self) -> bool:
        """Mamba-2 layers beside grouped-query attention layers
        (granitemoehybrid): models/granite_hybrid.py serves it."""
        return self.ssd_num_heads > 0

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def is_deepseek_v3(self) -> bool:
        """The v3 generation's attention-score and routing conventions
        (deepseek_v32 is v3 plus the indexer; kimi_k2 is v3's block at its
        own sizes)."""
        return self.model_type in ("deepseek_v3", "deepseek_v32", "kimi_k2",
                                   "dots3_note")

    @classmethod
    def from_hf_config(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        mt = str(cfg.get("model_type", "llama"))
        if mt == "phi4flash":
            return cls._from_phi4flash(cfg)
        if mt == "mimo_v2":
            return cls._from_mimo_v2(cfg)
        if mt == "exaone_moe":
            return cls._from_exaone_moe(cfg)
        if mt == "kimi_linear":
            return cls._from_kimi_linear(cfg)
        if mt == "granitemoehybrid":
            return cls._from_granitemoehybrid(cfg)
        # a family with no branch here falls through to the llama block:
        # right for its many renamings, wrong for one whose layers keep a
        # recurrent state or come in kinds this parser does not know. It
        # says so in keys the llama branch never reads, so refuse by name
        # instead of serving the nearest family's mathematics
        unread = [k for k in ("mb_per_layer", "linear_attn_config")
                  if cfg.get(k)]
        kinds = sorted(set(cfg.get("layer_types") or ())
                       - {"sliding_attention", "full_attention"})
        if unread or kinds:
            raise ValueError(
                f"model_type {mt!r} is not implemented: its config carries "
                + " and ".join(
                    ([f"the key(s) {', '.join(unread)}"] if unread else [])
                    + ([f"layer_types of kind {', '.join(kinds)}"]
                       if kinds else []))
                + ", which no model module here reads (state-space layers "
                "are served for phi4flash (Mamba-1) and granitemoehybrid "
                "(Mamba-2), linear-attention layers for kimi_linear); it "
                "is not parsed as llama")
        if mt.startswith("gemma") and mt not in ("gemma", "gemma2"):
            # gemma3+ has different norms/attention — half-detecting it
            # via the gemma defaults would load garbage silently
            raise ValueError(f"unsupported gemma variant {mt!r} "
                             "(gemma and gemma2 are implemented)")
        if mt != "qwen2_moe" and cfg.get("shared_expert_intermediate_size"):
            # an UNKNOWN family carrying a shared expert: the generic
            # expert-name matching would load the routed experts and
            # silently DROP the shared one — garbage logits, no error
            raise ValueError(
                f"unsupported shared-expert MoE family {mt!r} "
                f"(qwen2_moe is the implemented shared-expert family)")
        if (mt not in MOE_FAMILIES and any(cfg.get(k) for k in (
                "n_routed_experts", "num_local_experts", "num_experts"))):
            # the nearest family's routing function and attention would be
            # served under this one's name, silently
            raise ValueError(
                f"model_type {mt!r} is not implemented: its config carries "
                f"routed experts, and the expert families served here are "
                f"{', '.join(MOE_FAMILIES)}; it is not parsed as one of them")
        if mt == "kimi_k2":
            # v3's block (MLA with the q-LoRA pair, sigmoid noaux_tc routing
            # with the v3 score scale) at this family's own sizes: what
            # DeepseekV3Config would default is v3's, not this family's
            missing = [k for k in ("n_routed_experts", "n_group",
                                   "topk_group", "routed_scaling_factor",
                                   "first_k_dense_replace")
                       if cfg.get(k) is None]
            if missing:
                raise ValueError(
                    f"kimi_k2 needs {', '.join(missing)} in its config "
                    f"(deepseek_v3's class defaults are not this family's)")
            mt = "deepseek_v3"          # the v3 branch, from here on
        dots = mt == "dots3_note"
        if dots:
            cls._check_dots3_note(cfg)
            # one routing group, no multi-token-prediction layer served:
            # not v3's class defaults
            cfg = {"n_group": 1, "topk_group": 1,
                   "num_nextn_predict_layers": 0, **cfg}
        v32 = mt == "deepseek_v32" or dots
        if v32:
            # v3's block plus the lightning indexer: every v3 check below
            # applies, and the three indexer sizes must be there
            for key in ("index_n_heads", "index_head_dim", "index_topk"):
                if not cfg.get(key):
                    raise ValueError(
                        f"deepseek_v32 needs {key} (the lightning "
                        f"indexer's sizes); a config without them is "
                        f"deepseek_v3")
            if int(cfg["index_head_dim"]) < int(
                    cfg.get("qk_rope_head_dim", 64)):
                raise ValueError(
                    "deepseek_v32 index_head_dim is narrower than "
                    "qk_rope_head_dim: the indexer ropes its first "
                    "qk_rope_head_dim lanes")
            if not int(cfg.get("q_lora_rank", 1536) or 0):
                raise ValueError(
                    "deepseek_v32 without q_lora_rank is not implemented "
                    "(the indexer's queries project from the q-LoRA "
                    "latent)")
            mt = "deepseek_v3"          # the v3 branch, from here on
        elif any(cfg.get(k) for k in ("index_n_heads", "index_topk")):
            raise ValueError(
                f"{mt!r} with index_n_heads/index_topk is not implemented "
                f"(the indexer is deepseek_v32's)")
        if mt == "deepseek_v3":
            # models/mla.py implements exactly HF DeepseekV3's semantics:
            # sigmoid-scored noaux_tc routing, interleaved rope, bf16
            # weights — anything else must reject, not half-apply
            if str(cfg.get("scoring_func", "sigmoid")) != "sigmoid":
                raise ValueError(
                    f"deepseek_v3 scoring_func "
                    f"{cfg.get('scoring_func')!r} is not implemented "
                    f"(sigmoid is the v3 routing models/mla.py carries)")
            tm3 = cfg.get("topk_method", "noaux_tc")
            if tm3 != "noaux_tc":
                raise ValueError(
                    f"deepseek_v3 topk_method {tm3!r} is not implemented "
                    f"(noaux_tc is)")
            if cfg.get("rope_interleave") is False:
                # HF default is True (the released-checkpoint layout);
                # half-split rope on interleaved weights decodes garbage
                raise ValueError(
                    "deepseek_v3 rope_interleave=false is not "
                    "implemented (the interleaved rotation is)")
            if cfg.get("quantization_config"):
                raise ValueError(
                    "deepseek_v3 fp8 block-quantized checkpoints "
                    "(quantization_config) are not implemented — load a "
                    "bf16 conversion (engine-side int8/int4 weight "
                    "quantization is applied at load, not from fp8)")
        if mt == "deepseek_v2":
            tm = cfg.get("topk_method", "greedy")
            if cfg.get("n_routed_experts") and tm not in (
                    "greedy", "group_limited_greedy"):
                raise ValueError(
                    f"deepseek_v2 topk_method {tm!r} is not implemented "
                    f"(greedy and group_limited_greedy are)")
            if cfg.get("norm_topk_prob"):
                # transformers' native DeepseekV2 gate reads but never
                # APPLIES norm_topk_prob (4.57.6), while the original
                # remote code renorms instead of scaling — the combined
                # semantics are unpinned, so reject rather than guess
                raise ValueError(
                    "deepseek_v2 norm_topk_prob=true is not implemented "
                    "(reference semantics are unpinned; released V2 "
                    "configs use false)")
        if mt == "qwen3_moe" and not cfg.get("norm_topk_prob", False):
            # moe_mlp implements the normalized (mixtral-equivalent)
            # routing convention; softmax-then-topk WITHOUT renorm is a
            # different function and would decode garbage silently. HF's
            # Qwen3MoeConfig DEFAULTS the key to false, so an absent key
            # must reject too (released checkpoints set it true).
            raise ValueError("qwen3_moe requires norm_topk_prob=true "
                             "(routing weights must renormalize over "
                             "the top-k)")
        if mt in ("qwen2_moe", "qwen3_moe") and (
                cfg.get("mlp_only_layers")
                or int(cfg.get("decoder_sparse_step", 1) or 1) > 1):
            # hybrid dense/sparse layer mixes cannot be represented by
            # the uniform stacked expert tensors; failing here beats a
            # misleading "checkpoint missing experts" later
            raise ValueError(f"{mt} hybrid sparsity (mlp_only_layers "
                             "/ decoder_sparse_step > 1) is not supported "
                             "— every layer must be sparse")
        if mt == "phi3" and cfg.get("rope_scaling"):
            # phi3 128k variants: longrope ("su" is the same function's
            # legacy name in early Phi-3 configs). Anything else would
            # half-apply a different rope and decode garbage.
            rrs = cfg["rope_scaling"]
            if _rope_type(rrs) != "longrope":
                raise ValueError(
                    f"phi3 rope_scaling type {_rope_type(rrs)!r} is not "
                    f"implemented (longrope is)")
            d2 = int(cfg.get("head_dim",
                             int(cfg.get("hidden_size", 4096))
                             // int(cfg.get("num_attention_heads", 32))
                             )) // 2
            sf, lf = rrs.get("short_factor"), rrs.get("long_factor")
            if (not sf or not lf or len(sf) != d2 or len(lf) != d2):
                raise ValueError(
                    f"phi3 longrope needs short_factor and long_factor "
                    f"of length head_dim/2 = {d2} (got "
                    f"{len(sf or [])}/{len(lf or [])})")
            if not cfg.get("original_max_position_embeddings"):
                raise ValueError(
                    "phi3 longrope needs top-level "
                    "original_max_position_embeddings (the pretrained "
                    "window the factor switch and attention scaling "
                    "derive from)")
        n_heads = int(cfg.get("num_attention_heads", 32))
        hidden = int(cfg.get("hidden_size", 4096))
        is_ds = mt in ("deepseek_v2", "deepseek_v3")
        # HF save_pretrained omits class-default keys (to_diff_dict), so
        # absent MoE keys must take each FAMILY's class defaults —
        # otherwise a re-saved MoE config silently parses as dense
        n_experts = int(cfg.get("num_local_experts", 0)
                        or cfg.get("n_routed_experts", 0)     # deepseek
                        or cfg.get("num_experts",
                                   {"qwen2_moe": 60, "qwen3_moe": 128,
                                    "mixtral": 8,
                                    # DeepseekV3Config class default —
                                    # every released V3/R1 is MoE
                                    "deepseek_v3": 256}.get(mt, 0)) or 0)
        moe_inter = int(cfg.get("moe_intermediate_size",
                                {"qwen2_moe": 1408, "qwen3_moe": 768,
                                 # DeepseekV2Config class default (1407!)
                                 "deepseek_v2": 1407,
                                 "deepseek_v3": 2048}.get(mt, 0)) or 0)
        rs = None
        raw_rs = cfg.get("rope_scaling")
        if isinstance(raw_rs, dict):
            rs = RopeScaling(
                rope_type=_rope_type(raw_rs),
                factor=float(raw_rs.get("factor", 1.0)),
                low_freq_factor=float(raw_rs.get("low_freq_factor", 1.0)),
                high_freq_factor=float(raw_rs.get("high_freq_factor", 4.0)),
                # phi3 carries original_max at the TOP level, llama3/yarn
                # inside rope_scaling
                original_max_position_embeddings=int(
                    raw_rs.get(
                        "original_max_position_embeddings",
                        cfg.get("original_max_position_embeddings",
                                8192))),
                short_factor=tuple(raw_rs.get("short_factor") or ()),
                long_factor=tuple(raw_rs.get("long_factor") or ()),
                mscale=float(raw_rs.get("mscale", 0.0) or 0.0),
                mscale_all_dim=float(raw_rs.get("mscale_all_dim", 0.0)
                                     or 0.0),
                beta_fast=float(raw_rs.get("beta_fast", 32) or 32),
                beta_slow=float(raw_rs.get("beta_slow", 1) or 1),
                attention_factor=float(
                    raw_rs.get("attention_factor", 0.0) or 0.0),
            )
        # one chip's share of the experts: n_routed_experts counts those
        # held here, n_routed_experts_published the router's width (their
        # quotient is the number of shares) and expert_share_index says
        # which share this is (both travel in the served config.json)
        n_total = int(cfg.get("n_routed_experts_published") or 0)
        share_index = int(cfg.get("expert_share_index") or 0)
        if n_total or share_index:
            if not is_ds or not n_experts:
                raise ValueError(
                    "an expert share (n_routed_experts_published / "
                    "expert_share_index) is implemented for the deepseek "
                    "MoE block only")
            if (n_total % n_experts
                    or not 0 <= share_index < n_total // n_experts):
                raise ValueError(
                    f"expert share {share_index} holding {n_experts} "
                    f"experts does not divide the published {n_total}")
            if n_total == n_experts:
                n_total = 0             # one share of one: nothing cut
        return cls(
            model_type=cfg.get("model_type", "llama"),
            vocab_size=int(cfg.get("vocab_size", 32000)),
            hidden_size=hidden,
            # MoE families size the EXPERT mlps by moe_intermediate_size;
            # our stacked expert tensors use intermediate_size for F
            intermediate_size=int(
                moe_inter if (moe_inter and n_experts > 0)
                else cfg.get("intermediate_size", 4 * hidden)),
            num_layers=int(cfg.get("num_hidden_layers", 32)),
            num_heads=n_heads,
            num_kv_heads=int(cfg.get("num_key_value_heads", n_heads)),
            head_dim=int(cfg.get("head_dim", hidden // n_heads)),
            max_position_embeddings=int(cfg.get("max_position_embeddings", 4096)),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            rope_theta=float(cfg.get("rope_theta", 10000.0)),
            rope_scaling=rs,
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            # HF Qwen2/Qwen2Moe hardcode qkv bias in the modeling code and
            # ship no attention_bias key, so default it on for them
            attention_bias=bool(cfg.get(
                "attention_bias",
                cfg.get("model_type") in ("qwen2", "qwen2_moe"))),
            num_experts=n_experts,
            # HF save_pretrained omits default-valued keys (use_diff), so
            # each family's OWN default must apply when the key is absent:
            # Mixtral 2, Qwen2Moe 4, Qwen3Moe 8
            num_experts_per_tok=int(cfg.get(
                "num_experts_per_tok",
                {"qwen2_moe": 4, "qwen3_moe": 8,
                 "deepseek_v3": 8}.get(mt, 2))),
            # qwen2_moe DEFAULTS norm_topk_prob=false (weights are the
            # all-expert softmax values, not renormalized); deepseek_v2
            # never renormalizes; deepseek_v3 defaults TRUE (HF
            # DeepseekV3TopkRouter applies it for real); every other
            # family renormalizes over the top-k
            moe_norm_topk=(bool(cfg.get("norm_topk_prob", False))
                           if mt == "qwen2_moe"
                           else False if mt == "deepseek_v2"
                           else bool(cfg.get("norm_topk_prob", True))
                           if mt == "deepseek_v3" else True),
            # the qwen2_moe architecture ALWAYS has a shared expert (HF
            # modeling code is unconditional); an absent key means the
            # HF-default size 5632, NOT "no shared expert" — silently
            # dropping it would be the garbage-logits hazard the
            # unknown-family guard above rejects
            shared_expert_size=int(
                # deepseek: n_shared_experts × the expert width,
                # additive; the ABSENT key means the class default (2
                # for v2, 1 for v3 — to_diff_dict omits defaults), NOT
                # "no shared experts"
                int(cfg.get("n_shared_experts",
                            2 if mt == "deepseek_v2" else 1) or 0)
                * moe_inter
                if is_ds else
                cfg.get("shared_expert_intermediate_size",
                        5632 if mt == "qwen2_moe" else 0) or 0),
            qk_norm=bool(cfg.get("qk_norm", cfg.get("model_type")
                         in ("qwen3", "qwen3_moe"))),
            # hidden_activation is authoritative when present; gemma-1 hub
            # configs ship a stale hidden_act="gelu" that HF itself
            # overrides to the tanh-approx gelu at runtime
            hidden_act=(cfg.get("hidden_activation")
                        or ("gelu_pytorch_tanh"
                            if str(cfg.get("model_type", "")).startswith(
                                "gemma")
                            else cfg.get("hidden_act") or "silu")),
            embed_scale=str(cfg.get("model_type", "")).startswith("gemma"),
            norm_plus_one=str(cfg.get("model_type", "")).startswith("gemma"),
            post_norms=cfg.get("model_type") == "gemma2",
            attn_logit_softcap=(float(cfg["attn_logit_softcapping"])
                                if cfg.get("attn_logit_softcapping")
                                else None),
            final_logit_softcap=(float(cfg["final_logit_softcapping"])
                                 if cfg.get("final_logit_softcapping")
                                 else None),
            query_pre_attn_scalar=(float(cfg["query_pre_attn_scalar"])
                                   if cfg.get("query_pre_attn_scalar")
                                   else None),
            # the five MLA dims share class defaults across both
            # DeepseekV2Config and DeepseekV3Config (512/1536/64/128/
            # 128) — absent keys in a re-saved config mean THOSE, not
            # "no MLA" (an explicit null q_lora_rank is the -Lite
            # plain-q_proj layout, hence `or 0`)
            moe_routing=("sigmoid_noaux" if mt == "deepseek_v3"
                         else "softmax"),
            num_nextn_predict_layers=int(
                cfg.get("num_nextn_predict_layers", 1) or 0)
            if mt == "deepseek_v3" else 0,
            index_n_heads=int(cfg["index_n_heads"]) if v32 else 0,
            index_head_dim=int(cfg["index_head_dim"]) if v32 else 0,
            index_topk=int(cfg["index_topk"]) if v32 else 0,
            num_experts_total=n_total,
            expert_share_index=share_index,
            q_lora_rank=int(cfg.get("q_lora_rank",
                                    1536 if is_ds else 0) or 0),
            kv_lora_rank=int(cfg.get("kv_lora_rank", 512) or 0)
            if is_ds else 0,
            qk_nope_head_dim=int(cfg.get(
                "qk_nope_head_dim", 128 if is_ds else 0) or 0),
            qk_rope_head_dim=int(cfg.get(
                "qk_rope_head_dim", 64 if is_ds else 0) or 0),
            v_head_dim=int(cfg.get("v_head_dim",
                                   128 if is_ds else 0) or 0),
            first_k_dense=int(cfg.get(
                "first_k_dense_replace",
                3 if mt == "deepseek_v3" else 0) or 0)
            if n_experts > 0 else 0,
            dense_intermediate_size=int(
                cfg.get("intermediate_size",
                        18432 if mt == "deepseek_v3" else 0) or 0)
            if is_ds and n_experts > 0 else 0,
            routed_scaling=float(
                cfg.get("routed_scaling_factor",
                        2.5 if mt == "deepseek_v3" else 1.0) or 1.0),
            n_group=int(cfg.get("n_group") or 0)
            if cfg.get("topk_method") == "group_limited_greedy"
            else int(cfg.get("n_group", 8) or 0)
            if mt == "deepseek_v3" else 0,
            topk_group=int(cfg.get("topk_group") or 0)
            if cfg.get("topk_method") == "group_limited_greedy"
            else int(cfg.get("topk_group", 4) or 0)
            if mt == "deepseek_v3" else 0,
            **(cls._dots3_note_fields(cfg) if dots else {}),
            sliding_window=(int(cfg.get("sliding_window") or 4096)
                            if mt == "gemma2"
                            else int(cfg["sliding_window"])
                            if mt == "phi3" and cfg.get("sliding_window")
                            else None),
            # phi3 windows EVERY layer (HF Phi3Attention), unlike
            # gemma2's interleave — synthesize explicit layer_types so
            # sliding_layer_mask can't fall back to the gemma2 default
            layer_types=(list(cfg["layer_types"][:int(
                cfg["num_hidden_layers"])]) if dots
                         else cfg.get("layer_types")
                         or (["sliding_attention"]
                             * int(cfg.get("num_hidden_layers", 32))
                             if mt == "phi3" and cfg.get("sliding_window")
                             else None)),
        )

    @staticmethod
    def _check_dots3_note(cfg: Dict[str, Any]) -> None:
        """dots3_note: v3.2's block on its "full_attention" layers, a latent
        geometry of its own (the swa_* keys) on its "sliding_attention"
        ones. Nothing of v3's class defaults is this family's: every size
        must be in the file, and the layer kinds must be ones the program
        runs."""
        swa = ("swa_num_attention_heads", "swa_q_lora_rank",
               "swa_kv_lora_rank", "swa_qk_nope_head_dim",
               "swa_qk_rope_head_dim", "swa_v_head_dim", "swa_rope_theta")
        missing = [k for k in (
            "n_routed_experts", "routed_scaling_factor",
            "first_k_dense_replace", "layer_types", "sliding_window_size",
            "num_hidden_layers", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "rope_theta") + swa if cfg.get(k) is None]
        if missing:
            raise ValueError(
                f"dots3_note needs {', '.join(missing)} in its config "
                f"(deepseek_v3's class defaults are not this family's)")
        n = int(cfg["num_hidden_layers"])
        kinds = list(cfg["layer_types"])
        problems = []
        if len(kinds) < n:
            problems.append(f"layer_types names {len(kinds)} layers of "
                            f"num_hidden_layers {n}")
        kinds = kinds[:n]                 # a cut depth keeps the leading ones
        dense = int(cfg["first_k_dense_replace"])
        if any(k != "full_attention" for k in kinds[:dense]):
            problems.append(
                f"layer_types: the first_k_dense_replace = {dense} dense "
                f"layer(s) must be full_attention (the window layers' "
                f"stacks hold expert layers only)")
        if "sliding_attention" not in kinds:
            problems.append("layer_types has no sliding_attention layer "
                            "(that model is deepseek_v32)")
        if int(cfg.get("index_topk") or 0) and "full_attention" not in kinds:
            problems.append("index_topk > 0 with no full_attention layer in "
                            "layer_types: the indexer selects for those")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if cfg.get(key, "headwise") != "headwise":
                problems.append(f"{key} {cfg[key]!r} (headwise is "
                                f"implemented)")
        if cfg.get("rope_scaling"):
            problems.append("rope_scaling (the two thetas are read "
                            "unscaled)")
        if int(cfg.get("moe_layer_freq", 1) or 1) != 1:
            problems.append("moe_layer_freq other than 1")
        if int(cfg["sliding_window_size"]) < 1:
            problems.append("sliding_window_size < 1")
        if problems:
            raise ValueError("dots3_note is not implemented with: "
                             + "; ".join(problems))

    @staticmethod
    def _dots3_note_fields(cfg: Dict[str, Any]) -> Dict[str, Any]:
        return dict(
            swa_num_heads=int(cfg["swa_num_attention_heads"]),
            swa_q_lora_rank=int(cfg["swa_q_lora_rank"]),
            swa_kv_lora_rank=int(cfg["swa_kv_lora_rank"]),
            swa_qk_nope_head_dim=int(cfg["swa_qk_nope_head_dim"]),
            swa_qk_rope_head_dim=int(cfg["swa_qk_rope_head_dim"]),
            swa_v_head_dim=int(cfg["swa_v_head_dim"]),
            swa_rope_theta=float(cfg["swa_rope_theta"]),
            # counts the query's own position: 513 keys (assumed reading 3)
            swa_window=int(cfg["sliding_window_size"]),
            attention_gate=True,
            mla_lora_rescale=bool(cfg.get("apply_mla_qkv_lora_rescale")))

    @classmethod
    def _from_mimo_v2(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """MiMo-V2's published keys (models/mimo.py): full and window
        grouped-query layers by ``hybrid_layer_pattern`` (0 full, 1
        window), dense and expert MLPs by ``moe_layer_freq`` (0 dense, 1
        experts), both lists. No family's class defaults: every size is
        the file's, and what the program does not run is refused by name."""
        need = ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "v_head_dim",
                "swa_num_attention_heads", "swa_num_key_value_heads",
                "swa_head_dim", "swa_v_head_dim", "swa_rope_theta",
                "rope_theta", "sliding_window", "hybrid_layer_pattern",
                "moe_layer_freq", "intermediate_size",
                "moe_intermediate_size", "n_routed_experts",
                "num_experts_per_tok", "partial_rotary_factor",
                "vocab_size")
        missing = [k for k in need if cfg.get(k) is None]
        if missing:
            raise ValueError(f"mimo_v2 needs {', '.join(missing)} in its "
                             f"config (no family's class defaults are "
                             f"this one's)")
        n = int(cfg["num_hidden_layers"])
        pattern = list(cfg["hybrid_layer_pattern"])
        freq = list(cfg["moe_layer_freq"])
        problems = []
        for key, lst in (("hybrid_layer_pattern", pattern),
                         ("moe_layer_freq", freq)):
            if len(lst) < n:
                problems.append(f"{key} names {len(lst)} layers of "
                                f"num_hidden_layers {n}")
            if any(v not in (0, 1) for v in lst):
                problems.append(f"{key} holds entries other than 0 and 1")
        # a cut depth keeps the leading entries
        pattern, freq = pattern[:n], freq[:n]
        dense = next((i for i, f in enumerate(freq) if f), len(freq))
        if dense == 0 or (pattern and pattern[0] != 0):
            problems.append(
                "a leading layer that is not dense or not full attention "
                "(moe_layer_freq[0] and hybrid_layer_pattern[0] must be 0: "
                "the window layers' stacks hold expert layers only)")
        if any(f == 0 for f in freq[dense:]):
            problems.append("moe_layer_freq with a dense layer behind an "
                            "expert layer")
        if any(pattern[:dense]):
            problems.append("hybrid_layer_pattern: a window layer among the "
                            "leading dense layers")
        if 1 not in pattern:
            problems.append("hybrid_layer_pattern has no window layer "
                            "(that model is a plain grouped-query one)")
        if cfg.get("add_full_attention_sink_bias"):
            problems.append("add_full_attention_sink_bias (a sink on the "
                            "full layers)")
        if int(cfg.get("n_shared_experts") or 0):
            problems.append("n_shared_experts (a shared expert)")
        rs = cfg.get("rope_scaling") or {}
        if rs and _rope_type(rs) != "default":
            problems.append(f"rope_scaling {_rope_type(rs)!r} (the two "
                            f"bases are read unscaled)")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid" or cfg.get(
                "topk_method", "noaux_tc") != "noaux_tc":
            problems.append("a routing other than sigmoid / noaux_tc")
        if int(cfg.get("n_group") or 1) != 1 or int(
                cfg.get("topk_group") or 1) != 1:
            problems.append("n_group / topk_group other than 1")
        if cfg.get("attention_bias"):
            problems.append("attention_bias")
        if int(cfg.get("sliding_window_size") or cfg["sliding_window"]) \
                != int(cfg["sliding_window"]) or int(
                    cfg["sliding_window"]) < 1:
            problems.append("sliding_window and sliding_window_size that "
                            "differ, or a window < 1")
        H, KVH = int(cfg["num_attention_heads"]), int(
            cfg["num_key_value_heads"])
        Hs, KVHs = int(cfg["swa_num_attention_heads"]), int(
            cfg["swa_num_key_value_heads"])
        if H % KVH or Hs % KVHs or KVH < 1 or KVHs < 1:
            problems.append("query heads that the key/value heads do not "
                            "divide")
        dk = int(cfg["head_dim"])
        rot = int(dk * float(cfg["partial_rotary_factor"]))
        rot -= rot % 2
        if int(cfg["swa_head_dim"]) != dk:
            problems.append("swa_head_dim other than head_dim (one rotary "
                            "width is read for both kinds)")
        if not 2 <= rot <= dk:
            problems.append(f"partial_rotary_factor gives {rot} rotating "
                            f"lanes of {dk}")
        if problems:
            raise ValueError("mimo_v2 is not implemented with: "
                             + "; ".join(problems))
        n_experts = int(cfg["n_routed_experts"])
        n_total = int(cfg.get("n_routed_experts_published") or 0)
        share = int(cfg.get("expert_share_index") or 0)
        if n_total and (n_total % n_experts
                        or not 0 <= share < n_total // n_experts):
            raise ValueError(
                f"mimo_v2: n_routed_experts {n_experts} is not a share of "
                f"n_routed_experts_published {n_total}, or "
                f"expert_share_index {share} is outside it")
        return cls(
            model_type="mimo_v2",
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["moe_intermediate_size"]),
            dense_intermediate_size=int(cfg["intermediate_size"]),
            num_layers=n, num_heads=H, num_kv_heads=KVH, head_dim=dk,
            v_head_dim=int(cfg["v_head_dim"]),
            max_position_embeddings=int(
                cfg.get("max_position_embeddings", 1048576)),
            rms_norm_eps=float(cfg.get("layernorm_epsilon", 1e-5)),
            rope_theta=float(cfg["rope_theta"]),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            hidden_act=str(cfg.get("hidden_act") or "silu"),
            num_experts=n_experts,
            num_experts_total=n_total if n_total != n_experts else 0,
            expert_share_index=share,
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
            moe_routing="sigmoid_noaux", n_group=1, topk_group=1,
            routed_scaling=float(cfg.get("routed_scaling_factor") or 1.0),
            first_k_dense=dense,
            layer_types=["sliding_attention" if p else "full_attention"
                         for p in pattern],
            swa_num_heads=Hs, swa_num_kv_heads=KVHs,
            swa_head_dim=int(cfg["swa_head_dim"]),
            swa_v_head_dim=int(cfg["swa_v_head_dim"]),
            swa_rope_theta=float(cfg["swa_rope_theta"]),
            # counts the query's own position: 128 keys (assumed)
            swa_window=int(cfg["sliding_window"]),
            swa_sink=bool(cfg.get("add_swa_attention_sink_bias")),
            rotary_dim=rot,
            value_scale=float(cfg.get("attention_value_scale") or 1.0))

    @classmethod
    def _from_exaone_moe(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """K-EXAONE's published keys (models/mimo.py): window and full
        grouped-query layers by ``layer_types``, dense and expert MLPs by
        ``mlp_layer_types``, the windows by ``sliding_windows``, all lists;
        ``mtp_layer_types`` / ``num_nextn_predict_layers`` say what
        multi-token-prediction modules the checkpoint holds. Every size is
        the file's, and what the program does not run is refused by name."""
        need = ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "num_key_value_heads", "head_dim", "layer_types",
                "mlp_layer_types", "sliding_window", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_tok", "rope_parameters", "vocab_size")
        missing = [k for k in need if cfg.get(k) is None]
        if missing:
            raise ValueError(f"exaone_moe needs {', '.join(missing)} in its "
                             f"config (no family's class defaults are "
                             f"this one's)")
        n = int(cfg["num_hidden_layers"])
        window = int(cfg["sliding_window"])
        kinds = list(cfg["layer_types"])
        mlps = list(cfg["mlp_layer_types"])
        windows = list(cfg.get("sliding_windows") or [
            window if t == "sliding_attention" else 0 for t in kinds])
        problems = []
        for key, lst in (("layer_types", kinds), ("mlp_layer_types", mlps),
                         ("sliding_windows", windows)):
            if len(lst) < n:
                problems.append(f"{key} names {len(lst)} layers of "
                                f"num_hidden_layers {n}")
        # a cut depth keeps the leading entries
        kinds, mlps, windows = kinds[:n], mlps[:n], windows[:n]
        other = sorted(set(kinds) - {"sliding_attention", "full_attention"})
        if other:
            problems.append(f"layer_types of kind {', '.join(other)}")
        if set(mlps) - {"dense", "sparse"}:
            problems.append("mlp_layer_types other than dense / sparse")
        if any(w != (window if t == "sliding_attention" else 0)
               for t, w in zip(kinds, windows)):
            problems.append("sliding_windows that differ from sliding_window "
                            "on a sliding layer or from 0 on a full one")
        dense = next((i for i, m in enumerate(mlps) if m == "sparse"),
                     len(mlps))
        if dense == 0 or "dense" in mlps[dense:]:
            problems.append("mlp_layer_types without a leading dense layer, "
                            "or with a dense layer behind a sparse one")
        if "sliding_attention" not in kinds or window < 1:
            problems.append("no sliding layer, or a window < 1 (that model "
                            "is a plain grouped-query one)")
        rp = cfg["rope_parameters"] or {}
        if _rope_type(rp) != "default":
            problems.append(f"rope_parameters of type {_rope_type(rp)!r}")
        if cfg.get("scoring_func", "sigmoid") != "sigmoid":
            problems.append("a scoring_func other than sigmoid")
        if int(cfg.get("n_group") or 1) != 1 or int(
                cfg.get("topk_group") or 1) != 1:
            problems.append("n_group / topk_group other than 1")
        if cfg.get("attention_bias"):
            problems.append("attention_bias")
        H, KVH = int(cfg["num_attention_heads"]), int(
            cfg["num_key_value_heads"])
        if KVH < 1 or H % KVH:
            problems.append("query heads that the key/value heads do not "
                            "divide")
        mtp = int(cfg.get("num_nextn_predict_layers") or 0)
        mtp_kinds = list(cfg.get("mtp_layer_types") or [])[:mtp]
        if mtp > 1 or (mtp and mtp_kinds != ["full_attention"]) or any(
                cfg.get("mtp_sliding_windows") or ()):
            problems.append("more than one multi-token-prediction module, "
                            "or one that is not of kind full_attention")
        if problems:
            raise ValueError("exaone_moe is not implemented with: "
                             + "; ".join(problems))
        n_experts = int(cfg["num_experts"])
        n_total = int(cfg.get("num_experts_published") or 0)
        share = int(cfg.get("expert_share_index") or 0)
        if n_total and (n_total % n_experts
                        or not 0 <= share < n_total // n_experts):
            raise ValueError(
                f"exaone_moe: num_experts {n_experts} is not a share of "
                f"num_experts_published {n_total}, or expert_share_index "
                f"{share} is outside it")
        dk = int(cfg["head_dim"])
        theta = float(rp.get("rope_theta", cfg.get("rope_theta") or 1e6))
        return cls(
            model_type="exaone_moe",
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["moe_intermediate_size"]),
            dense_intermediate_size=int(cfg["intermediate_size"]),
            num_layers=n, num_heads=H, num_kv_heads=KVH, head_dim=dk,
            v_head_dim=dk,
            max_position_embeddings=int(
                cfg.get("max_position_embeddings", 262144)),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            rope_theta=theta,
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            hidden_act=str(cfg.get("hidden_act") or "silu"),
            num_experts=n_experts,
            num_experts_total=n_total if n_total != n_experts else 0,
            expert_share_index=share,
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_norm_topk=bool(cfg.get("norm_topk_prob", True)),
            moe_routing="sigmoid_noaux", n_group=1, topk_group=1,
            routed_scaling=float(cfg.get("routed_scaling_factor") or 1.0),
            shared_expert_size=int(cfg.get("num_shared_experts") or 0)
            * int(cfg["moe_intermediate_size"]),
            first_k_dense=dense, layer_types=kinds,
            # ONE head geometry: the window layers' is the full layers'
            swa_num_heads=H, swa_num_kv_heads=KVH, swa_head_dim=dk,
            swa_v_head_dim=dk, swa_rope_theta=theta,
            # counts the query's own position: 128 keys (assumed)
            swa_window=window,
            qk_norm=True, norm_on_output=True, nope_full=True,
            mtp_layers=mtp)

    @classmethod
    def _from_kimi_linear(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """Kimi-Linear's published keys (models/kimi_linear.py): which
        layers are Kimi Delta Attention and which latent attention by the
        two 1-based lists of ``linear_attn_config``, the latent block's
        sizes under DeepSeek's names with ``q_lora_rank`` null and
        ``mla_use_nope``, the experts under this family's own names
        (``num_experts``, ``num_experts_per_token``, ``moe_renormalize``,
        ``moe_router_activation_func``). A cut depth keeps the entries of
        both lists up to it. What the program does not run is refused by
        name."""
        need = ("hidden_size", "num_hidden_layers", "num_attention_heads",
                "linear_attn_config", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "moe_intermediate_size", "num_experts",
                "num_experts_per_token", "first_k_dense_replace",
                "routed_scaling_factor", "vocab_size")
        missing = [k for k in need if cfg.get(k) is None]
        if missing:
            raise ValueError(f"kimi_linear needs {', '.join(missing)} in its "
                             f"config (no family's class defaults are this "
                             f"one's)")
        n = int(cfg["num_hidden_layers"])
        lin = dict(cfg["linear_attn_config"])
        kda = [int(i) for i in lin.get("kda_layers") or () if int(i) <= n]
        full = [int(i) for i in lin.get("full_attn_layers") or ()
                if int(i) <= n]
        problems = []
        both = sorted(set(kda) & set(full))
        if both:
            problems.append(f"linear_attn_config names layer(s) "
                            f"{', '.join(map(str, both))} in kda_layers AND "
                            f"in full_attn_layers")
        absent = sorted(set(range(1, n + 1)) - set(kda) - set(full))
        if absent or min(kda + full, default=1) < 1:
            problems.append(f"linear_attn_config's lists do not cover the "
                            f"layers 1..{n} (missing: "
                            f"{', '.join(map(str, absent)) or 'none'})")
        if len(set(kda)) != len(kda) or len(set(full)) != len(full):
            problems.append("linear_attn_config names a layer twice in one "
                            "list")
        if not kda or not full:
            problems.append("linear_attn_config without a kda layer or "
                            "without a full-attention layer inside the depth "
                            "(the plain latent model is deepseek_v3's)")
        for key in ("num_heads", "head_dim", "short_conv_kernel_size"):
            if not lin.get(key):
                problems.append(f"linear_attn_config.{key} is missing")
        if cfg.get("q_lora_rank"):
            problems.append("q_lora_rank (the published model has a plain "
                            "q projection)")
        if not cfg.get("mla_use_nope", False):
            problems.append("mla_use_nope false (rotated pe lanes are not "
                            "this family's)")
        if cfg.get("rope_scaling"):
            problems.append("rope_scaling (nothing is rotated)")
        if str(cfg.get("moe_router_activation_func", "sigmoid")) != "sigmoid":
            problems.append("a moe_router_activation_func other than sigmoid")
        if int(cfg.get("num_expert_group") or 1) != 1 or int(
                cfg.get("topk_group") or 1) != 1:
            problems.append("num_expert_group / topk_group other than 1")
        if int(cfg.get("moe_layer_freq") or 1) != 1:
            problems.append("moe_layer_freq other than 1 (every layer behind "
                            "the dense prefix holds experts)")
        if int(cfg.get("num_nextn_predict_layers") or 0):
            problems.append("num_nextn_predict_layers")
        if cfg.get("attention_bias"):
            problems.append("attention_bias")
        if problems:
            raise ValueError("kimi_linear is not implemented with: "
                             + "; ".join(problems))
        n_experts = int(cfg["num_experts"])
        n_total = int(cfg.get("num_experts_published") or 0)
        share = int(cfg.get("expert_share_index") or 0)
        if n_total and (n_total % n_experts
                        or not 0 <= share < n_total // n_experts):
            raise ValueError(
                f"kimi_linear: num_experts {n_experts} is not a share of "
                f"num_experts_published {n_total}, or expert_share_index "
                f"{share} is outside it")
        kda_set = set(kda)
        kinds = ["linear_attention" if i in kda_set else "full_attention"
                 for i in range(1, n + 1)]
        return cls(
            model_type="kimi_linear",
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["moe_intermediate_size"]),
            dense_intermediate_size=int(cfg["intermediate_size"]),
            num_layers=n, num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg.get("num_key_value_heads")
                             or cfg["num_attention_heads"]),
            head_dim=int(cfg["qk_nope_head_dim"])
            + int(cfg["qk_rope_head_dim"]),
            max_position_embeddings=int(
                cfg.get("model_max_length")
                or cfg.get("max_position_embeddings") or 1048576),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            rope_theta=float(cfg.get("rope_theta") or 10000.0),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", False)),
            hidden_act=str(cfg.get("hidden_act") or "silu"),
            q_lora_rank=0, kv_lora_rank=int(cfg["kv_lora_rank"]),
            qk_nope_head_dim=int(cfg["qk_nope_head_dim"]),
            qk_rope_head_dim=int(cfg["qk_rope_head_dim"]),
            v_head_dim=int(cfg["v_head_dim"]), mla_nope=True,
            num_experts=n_experts,
            num_experts_total=n_total if n_total != n_experts else 0,
            expert_share_index=share,
            num_experts_per_tok=int(cfg["num_experts_per_token"]),
            moe_norm_topk=bool(cfg.get("moe_renormalize", True)),
            moe_routing="sigmoid_noaux", n_group=1, topk_group=1,
            routed_scaling=float(cfg["routed_scaling_factor"]),
            shared_expert_size=int(cfg.get("num_shared_experts") or 0)
            * int(cfg["moe_intermediate_size"]),
            first_k_dense=int(cfg["first_k_dense_replace"]),
            layer_types=kinds,
            kda_num_heads=int(lin["num_heads"]),
            kda_head_dim=int(lin["head_dim"]),
            kda_conv_kernel=int(lin["short_conv_kernel_size"]))

    @classmethod
    def _from_granitemoehybrid(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """Granite 4.0-H's published keys (models/granite_hybrid.py): which
        layers are Mamba-2 and which attend by ``layer_types`` ("mamba" /
        "attention"; a cut depth keeps its first ``num_hidden_layers``
        entries), the state-space sizes under ``mamba_*``, the experts as
        ``num_local_experts`` of width ``intermediate_size`` beside one
        shared expert of ``shared_intermediate_size``, and the family's
        four multipliers. What the program does not run is refused by
        name."""
        need = ("hidden_size", "num_hidden_layers", "layer_types",
                "num_attention_heads", "num_key_value_heads",
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "num_local_experts", "num_experts_per_tok",
                "intermediate_size", "shared_intermediate_size",
                "vocab_size", "attention_multiplier",
                "embedding_multiplier", "residual_multiplier",
                "logits_scaling")
        missing = [k for k in need if cfg.get(k) is None]
        if missing:
            raise ValueError(f"granitemoehybrid needs {', '.join(missing)} "
                             f"in its config (no family's class defaults "
                             f"are this one's)")
        n = int(cfg["num_hidden_layers"])
        kinds = list(cfg["layer_types"])[:n]
        hidden = int(cfg["hidden_size"])
        heads, lanes = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
        problems = []
        if len(kinds) < n:
            problems.append(f"layer_types names {len(kinds)} layers of "
                            f"num_hidden_layers = {n}")
        other = sorted(set(kinds) - {"mamba", "attention"})
        if other:
            problems.append(f"layer_types of kind {', '.join(other)}")
        if "mamba" not in kinds or "attention" not in kinds:
            problems.append("layer_types without a mamba layer or without "
                            "an attention layer inside the depth (the cache "
                            "is a state group beside paged rows)")
        if str(cfg.get("position_embedding_type", "nope")) != "nope":
            problems.append("a position_embedding_type other than nope")
        if cfg.get("rope_scaling"):
            problems.append("rope_scaling (nothing is rotated)")
        if int(cfg.get("mamba_n_groups") or 1) != 1:
            problems.append("mamba_n_groups other than 1 (one B / C group "
                            "is what engine/ssd.py shares between heads)")
        if int(cfg.get("mamba_expand") or 2) * hidden != heads * lanes:
            problems.append("mamba_expand * hidden_size is not "
                            "mamba_n_heads * mamba_d_head")
        if cfg.get("mamba_proj_bias"):
            problems.append("mamba_proj_bias")
        if not cfg.get("mamba_conv_bias", True):
            problems.append("mamba_conv_bias false (the convolution is "
                            "served with its bias)")
        if cfg.get("attention_bias"):
            problems.append("attention_bias")
        if str(cfg.get("normalization_function", "rmsnorm")) != "rmsnorm":
            problems.append("a normalization_function other than rmsnorm")
        if str(cfg.get("hidden_act") or "silu") != "silu":
            problems.append("a hidden_act other than silu")
        if hidden % int(cfg["num_attention_heads"]):
            problems.append("hidden_size is not a multiple of "
                            "num_attention_heads (the head size is their "
                            "quotient)")
        if problems:
            raise ValueError("granitemoehybrid is not implemented with: "
                             + "; ".join(problems))
        return cls(
            model_type="granitemoehybrid",
            vocab_size=int(cfg["vocab_size"]), hidden_size=hidden,
            intermediate_size=int(cfg["intermediate_size"]),
            num_layers=n, num_heads=int(cfg["num_attention_heads"]),
            num_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=hidden // int(cfg["num_attention_heads"]),
            max_position_embeddings=int(
                cfg.get("max_position_embeddings") or 131072),
            rms_norm_eps=float(cfg.get("rms_norm_eps", 1e-5)),
            rope_theta=float(cfg.get("rope_theta") or 10000.0),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", True)),
            num_experts=int(cfg["num_local_experts"]),
            num_experts_per_tok=int(cfg["num_experts_per_tok"]),
            moe_norm_topk=True,
            shared_expert_size=int(cfg["shared_intermediate_size"]),
            nope_full=True,
            query_pre_attn_scalar=float(cfg["attention_multiplier"]) ** -2,
            layer_types=kinds,
            ssd_num_heads=heads, ssd_head_dim=lanes,
            ssd_d_state=int(cfg["mamba_d_state"]),
            ssd_conv_kernel=int(cfg["mamba_d_conv"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]))

    @classmethod
    def _from_phi4flash(cls, cfg: Dict[str, Any]) -> "ModelConfig":
        """Phi-4-mini-flash-reasoning's published keys. The Mamba sizes
        are the modelling code's constants (d_state 16, d_conv 4, expand
        2, dt_rank ceil(hidden / 16)); a config may state them."""
        hidden = int(cfg["hidden_size"])
        n_heads = int(cfg["num_attention_heads"])
        n_kv = int(cfg.get("num_key_value_heads", n_heads))
        layers = int(cfg["num_hidden_layers"])
        head_dim = int(cfg.get("head_dim") or hidden // n_heads)
        problems = []
        if int(cfg.get("mb_per_layer", 2)) != 2:
            problems.append("mb_per_layer must be 2 (state-space and "
                            "attention layers alternate)")
        if layers < 4 or layers % 2:
            problems.append("num_hidden_layers must be even and at least "
                            "4 (pairs of layers around the exporting "
                            "state-space layer and the full-attention one)")
        if not cfg.get("sliding_window"):
            problems.append("sliding_window is missing")
        if n_heads % 2 or n_kv % 2 or n_heads % n_kv:
            problems.append("differential attention pairs adjacent heads: "
                            "both head counts must be even and divide")
        if (int(cfg.get("mamba_expand", 2)) * hidden) % 128:
            problems.append("the state-space width (mamba_expand * "
                            "hidden_size) must be a multiple of 128 lanes "
                            "(engine/ssm.py)")
        if cfg.get("rope_scaling") or cfg.get("mlp_bias") \
                or cfg.get("lm_head_bias"):
            problems.append("rope_scaling / mlp_bias / lm_head_bias are "
                            "not implemented (the published config has "
                            "none)")
        if problems:
            raise ValueError("phi4flash: " + "; ".join(problems))
        return cls(
            model_type="phi4flash",
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=hidden,
            intermediate_size=int(cfg["intermediate_size"]),
            num_layers=layers, num_heads=n_heads, num_kv_heads=n_kv,
            head_dim=head_dim,
            max_position_embeddings=int(
                cfg.get("max_position_embeddings", 262144)),
            rms_norm_eps=float(cfg.get("layer_norm_eps", 1e-5)),
            tie_word_embeddings=bool(cfg.get("tie_word_embeddings", True)),
            attention_bias=True,
            hidden_act=str(cfg.get("hidden_act") or "silu"),
            sliding_window=int(cfg["sliding_window"]),
            mamba_d_state=int(cfg.get("mamba_d_state", 16)),
            mamba_d_conv=int(cfg.get("mamba_d_conv", 4)),
            mamba_expand=int(cfg.get("mamba_expand", 2)),
            mamba_dt_rank=int(cfg.get("mamba_dt_rank") or -(-hidden // 16)),
            mb_per_layer=2)

    @classmethod
    def from_model_dir(cls, model_dir: str) -> "ModelConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_config(json.load(f))


def bench_model_config(name: str) -> "ModelConfig":
    """The benchmark geometries, in ONE place so bench.py and
    tools/decode_profile.py measure the same model (they drifted when
    each carried its own literals). Unknown names raise — a typo must
    not silently profile the 1B fallback under the requested label."""
    if name == "tiny":
        return ModelConfig(vocab_size=2048, hidden_size=256,
                           intermediate_size=512, num_layers=4,
                           num_heads=8, num_kv_heads=4, head_dim=32,
                           max_position_embeddings=2048)
    if name == "1b":     # llama-3.2-1B shapes
        # 8192 positions (not the model's real 131k): the shared bench
        # geometry must cover tools/decode_profile.py's long-context
        # sweeps (PROF_SEQ up to ~8K) — 4096 silently capped them once
        # (ADVICE r3). RoPE-table cost at 8192 is negligible.
        return ModelConfig(vocab_size=128256, hidden_size=2048,
                           intermediate_size=8192, num_layers=16,
                           num_heads=32, num_kv_heads=8, head_dim=64,
                           max_position_embeddings=8192,
                           rope_theta=500000.0, tie_word_embeddings=True)
    if name == "8b":     # Llama-3-8B geometry (int8 ≈ 8 GB)
        return ModelConfig(vocab_size=128256, hidden_size=4096,
                           intermediate_size=14336, num_layers=32,
                           num_heads=32, num_kv_heads=8, head_dim=128,
                           max_position_embeddings=8192,
                           rope_theta=500000.0)
    if name == "70b_tp8shard":
        # The slice of Llama-3-70B (80L, D=8192, F=28672, H=64, KVH=8,
        # Dh=128, V=128256) that ONE chip owns under the production TP-8
        # pspecs (parallel/sharding.py param_pspecs: column-parallel
        # qkv/gate/up, row-parallel o/down, vocab-sharded embed+head):
        # 8 q heads, 1 kv head, F/8=3584, V/8=16032, full hidden — ≈8.9 GB
        # int8, the real per-chip HBM working set of the BASELINE.md
        # config-4 north star. Benching this geometry on the one real chip
        # measures the per-chip compute+HBM side of TP-8 decode; the
        # per-layer ICI collectives are priced separately
        # (parallel/ici_model.py) and bench.py reports the net number.
        return ModelConfig(vocab_size=16032, hidden_size=8192,
                           intermediate_size=3584, num_layers=80,
                           num_heads=8, num_kv_heads=1, head_dim=128,
                           max_position_embeddings=8192,
                           rope_theta=500000.0)
    if name == "moe":    # synthetic mixtral-class, one-chip (~4.7 GB)
        return ModelConfig(model_type="mixtral", vocab_size=32000,
                           hidden_size=2048, intermediate_size=5632,
                           num_layers=16, num_heads=32, num_kv_heads=8,
                           head_dim=64, max_position_embeddings=8192,
                           rope_theta=500000.0, num_experts=8,
                           num_experts_per_tok=2)
    if name == "qwen2moe":
        # qwen2_moe-class, one-chip (~3.1 GB int8): Qwen1.5-MoE-A2.7B's
        # D/L/heads/expert-F/shared-F with the expert COUNT cut 60 → 8
        # to fit (the shared-expert + unnormalized-routing code paths are
        # what this geometry times; expert count only scales the einsum)
        return ModelConfig(model_type="qwen2_moe", vocab_size=151936,
                           hidden_size=2048, intermediate_size=1408,
                           num_layers=24, num_heads=16, num_kv_heads=16,
                           head_dim=128, max_position_embeddings=8192,
                           attention_bias=True, num_experts=8,
                           num_experts_per_tok=4, moe_norm_topk=False,
                           shared_expert_size=5632)
    if name == "tiny_mla":
        # CI-sized MLA geometry: exercises the bench's MLA path (latent
        # {"kv"} pool, absorbed-decode flop accounting, hybrid MoE)
        # without the real weights (tests/test_bench_smoke.py)
        return ModelConfig(model_type="deepseek_v2", vocab_size=2048,
                           hidden_size=256, intermediate_size=128,
                           num_layers=4, num_heads=8, num_kv_heads=8,
                           head_dim=48, max_position_embeddings=2048,
                           q_lora_rank=0, kv_lora_rank=64,
                           qk_nope_head_dim=32, qk_rope_head_dim=16,
                           v_head_dim=32, num_experts=4,
                           num_experts_per_tok=2, moe_norm_topk=False,
                           first_k_dense=1, dense_intermediate_size=256,
                           shared_expert_size=256)
    if name == "mla":
        # DeepSeek-V2-Lite-class MLA geometry, one-chip (~3.3 GB int8):
        # Lite's D/L/heads/MLA dims/expert-F/shared/hybrid layout with
        # the expert COUNT cut 64 → 8 to fit (the qwen2moe precedent:
        # expert count only scales the dense-over-E einsum). What this
        # geometry times is the MLA serving win — the absorbed decode
        # reads ONE 576-lane latent row per token instead of
        # KVH·Dh·2 expanded lanes — plus the deepseek MoE block.
        return ModelConfig(model_type="deepseek_v2", vocab_size=102400,
                           hidden_size=2048, intermediate_size=1408,
                           num_layers=27, num_heads=16, num_kv_heads=16,
                           head_dim=192, max_position_embeddings=8192,
                           rope_theta=10000.0,
                           q_lora_rank=0, kv_lora_rank=512,
                           qk_nope_head_dim=128, qk_rope_head_dim=64,
                           v_head_dim=128, num_experts=8,
                           num_experts_per_tok=6, moe_norm_topk=False,
                           first_k_dense=1, dense_intermediate_size=10944,
                           shared_expert_size=2816)
    raise ValueError(f"unknown bench model {name!r} "
                     f"(tiny|tiny_mla|1b|8b|70b_tp8shard|moe|qwen2moe"
                     f"|mla)")


@dataclasses.dataclass
class EngineConfig:
    """Serving-engine knobs (the analog of the reference's engine flags,
    launch/dynamo-run/src/flags.rs, plus XLA-specific bucketing)."""

    max_model_len: int = 2048
    # 0 = auto-select at engine bring-up from the model geometry
    # (auto_kv_block_size: the round-5 small-C finding promoted from a
    # bench.py-only default — KVH·Dh <= 128 rows are DMA-latency-bound
    # at 16, a 64-token block quadruples the per-DMA payload)
    kv_block_size: int = 16
    num_kv_blocks: int = 512          # HBM KV pool size (blocks across all seqs)
    max_num_seqs: int = 8             # decode batch slots
    enable_prefix_reuse: bool = True  # match prompt blocks against the pool
    host_kv_blocks: int = 0           # host (TPU-VM DRAM) offload tier; 0 = off
    # persistent disk (G3) KV tier (llm/kv/diskstore.py): a
    # capacity-bounded content-addressed block store under kv_disk_dir.
    # Host-tier evictions spill there (async write-behind, bounded queue,
    # drop-on-backpressure); match_prefix cascades device → host → disk;
    # acknowledged blocks survive kill -9 and warm-start the next engine
    # pointed at the same dir. Requires host_kv_blocks > 0 (the disk tier
    # sits UNDER the host tier — spill feeds on its evictions).
    kv_disk_dir: str = ""
    kv_disk_blocks: int = 0           # disk tier capacity; 0 = off
    # remote (G4) fleet KV fabric (llm/kv/remotestore.py + fabric.py).
    # kv_remote_dir roots the object-store backend (GCS/S3-shaped,
    # filesystem-rooted — a mounted bucket in production): disk-tier
    # capacity evictions promote there write-behind (acknowledged iff
    # durable) and ANY worker pointed at the same root reuses them; the
    # peer-worker backend (another worker's disk over the kv_fabric RPC
    # plane) needs no dir and attaches at runtime (launch/run.py
    # --kv-fabric). Requires the disk tier (the promotion pump feeds on
    # its evictions). kv_remote_blocks 0 = unbounded object capacity.
    kv_remote_dir: str = ""
    kv_remote_blocks: int = 0
    # latency-aware admission for remote hits (fabric.AdmissionGate):
    # "auto" promotes only when modeled fetch beats modeled recompute;
    # "always"/"never" are ops overrides
    kv_remote_admission: str = "auto"
    # pace the offload pump's write-backs to this simulated d2h link
    # (GB/s); 0 = real link speed. Lets a CPU run exercise the tier under
    # a TPU-VM-like link (tools/bandwidth_model.py holds the analytic
    # tables)
    offload_simulated_gbps: float = 0.0
    prefill_buckets: List[int] = dataclasses.field(
        default_factory=lambda: [128, 256, 512, 1024, 2048])
    prefill_chunk: int = 0            # 0 = whole-prompt prefill
    dtype: str = "bfloat16"
    # parallelism over the device mesh
    tp: int = 1                       # tensor parallel (heads/mlp sharding)
    dp: int = 1                       # data parallel replicas inside one engine
    sp: int = 1                       # sequence parallel (ring attention) for prefill
    ep: int = 1                       # expert parallel (MoE)
    # pipeline parallel (parallel/pipeline_parallel.py): layer stacks +
    # KV pool shard L over a "pp" stage ring — the DCN-viable cross-host
    # axis. Decode runs TOKEN-INTERLEAVED: the batch splits into pp
    # microbatches round-robined through the stages so every rank
    # computes a live microbatch each tick (steady-state utilization
    # K·pp/(K·pp+pp-1) per dispatch, vs 1/pp for a bubbled loop), and
    # prefill chunks pipeline the same way. Composes with tp (in-stage
    # Megatron split + psum) only; requires decode_steps_per_dispatch>1,
    # max_num_seqs and every prefill bucket divisible by pp. Refused (at
    # bring-up, loudly): MLA, weight/KV quantization, speculative
    # decoding, sp, sliding-window families.
    pp: int = 1
    # shortest cold prefill worth the ring path (per-layer shard_map +
    # sp-1 ppermute rounds); shorter prompts stay on the chunked program
    sp_min_prefill_tokens: int = 512
    # decode steps fused into one XLA dispatch (lax.scan): tokens are
    # harvested to the host once per dispatch, so the device→host fetch
    # is amortized K×. K>1 trades step-granular EOS/cancel reaction (worst
    # case K-1 wasted steps per sequence) for throughput. At K = 1 (the
    # default) the engine keeps one step in flight: step n+1 is launched
    # off step n's on-device tokens before the loop fetches them, so the
    # fetch, the bookkeeping and the event loop's turn run under a step's
    # device time. A finish by max_tokens, context capacity or a cancel
    # already seen is known ahead and wastes nothing; a finish by EOS or
    # stop, or a cancel that arrives mid-step, is noticed one step later
    # than it is sampled (one slot-row of one step is discarded; the
    # client never sees it).
    decode_steps_per_dispatch: int = 1
    # K > 1 and ragged dispatch: defer each dispatch's harvest one
    # dispatch, as K = 1 always does: the next batch chains off on-device
    # tokens while the previous results copy to the host — steady-state
    # cost max(fetch, compute) instead of fetch+compute. Finish/cancel
    # reaction widens to ≤2K-1 steps. At K > 1 any change of the
    # slot→request map drains the pipeline (K = 1 chains per slot).
    # Redundant at K = 1 without ragged dispatch.
    # Note on exactness: under RECOMPUTE PREEMPTION (any dispatch mode,
    # pipelined or not) a stream is bit-exact vs an uncontended run only up
    # to its first preemption point — the re-admission prefill's f32
    # numerics differ slightly from the decode program's, which can flip a
    # greedy argmax at near-tie logits (root-caused via engine/replay.py;
    # previously misattributed to a pipelined-dispatch race).
    decode_dispatch_pipeline: bool = False
    # continuous-batching lane prefill: when the engine is ALREADY decoding,
    # an admission whose un-hit prompt suffix is <= this many tokens skips
    # the dedicated prefill program and instead rides the decode batch —
    # its prompt tokens are fed as "planned" inputs to the K-step decode
    # scan (one per step through its slot) and the transition to sampling
    # happens on device mid-dispatch. Decode throughput is unaffected by
    # admissions (prompt tokens are marginal extra batch rows on a
    # bandwidth-bound step) instead of stalling for a prefill dispatch.
    # Idle engines still use the dedicated prefill program (better TTFT:
    # one compute-bound dispatch instead of len(prompt) steps).
    # 0 disables; requires decode_steps_per_dispatch > 1.
    lane_prefill_max_tokens: int = 0
    # unified ragged dispatch (engine/ragged.py + models/*.ragged_forward;
    # docs/ragged_attention.md): ONE compiled program serves mixed
    # prefill+decode batches — the step loop packs pending prefill
    # chunks and due decode rows into a token-capacity-filled ragged
    # [sum(T_i)] batch, making continuous batching the only serving
    # code path. Admissions ride the batch lane-style (the sampled
    # first token comes from the ragged program, a recorded numeric
    # boundary exactly like lane prefill); per-row math is bit-exact
    # with the decode/lane programs. Kept OFF the following paths,
    # which fall back to / refuse loudly: disagg handoff + precomputed
    # admissions use the dedicated prefill program (their gather/
    # scatter contracts are prefill-shaped), and pp / sp / speculative
    # decoding / pipelined-dispatch composition is refused at
    # bring-up.
    ragged_dispatch: bool = False
    # token capacity of one ragged dispatch (the [sum(T_i)] row
    # budget, a compiled static shape). 0 = auto: max_num_seqs +
    # 2*ragged_max_seq_rows. Must cover one row per slot.
    ragged_max_tokens: int = 0
    # per-sequence row budget per dispatch: bounds the ragged kernel's
    # per-sequence VMEM q window (attention.ragged_supported) and how
    # much of one prompt a single dispatch may consume — longer
    # prompts stream across consecutive dispatches (each a chunked-
    # prefill continuation riding the decode batch)
    ragged_max_seq_rows: int = 64
    # speculative decoding (engine/spec/): max draft tokens verified per
    # dispatch; 0 = off. When > 0 the engine compiles a batched verify
    # program — [max_num_seqs, spec_k+1] query rows flattened through
    # the SAME paged decode forward, each row scattering its input
    # token's KV before attending positions <= its own — so k drafts
    # plus the bonus position score in ONE dispatch (the ragged
    # multi-token query shape; see docs/speculative.md). Acceptance is
    # lockstep token equality against per-position sampling keys:
    # greedy AND seeded sampling stay bit-exact vs plain decode.
    # Requests pick their own k <= spec_k via the `speculation` knob
    # (nvext.speculation on the OpenAI surface); llmctl spec set-k
    # retunes the live default within [0, spec_k].
    spec_k: int = 0
    # prompt-lookup drafter window: trailing n-gram lengths tried
    # (longest first) and how much history is searched
    spec_ngram_max: int = 4
    spec_ngram_min: int = 1
    spec_window: int = 1024
    # contiguity-aware KV layout (docs/kv_layout.md): the block pool's
    # run-tracking allocator (llm/kv/pool.py FreeRunIndex) always lands
    # new blocks as few maximal runs of adjacent ids; this knob gates
    # what EXPLOITS that — the decode kernel's run-coalesced DMA
    # (engine/attention.py wave_contig_table: one copy per contiguous
    # wave instead of one per block, the PERF round-5 "multi-block-per-
    # DMA" lever for small-C geometries) and the idle-time defrag pass
    # below. False = per-block DMAs always, no defrag (A/B escape
    # hatch; bench.py --kv-frag measures the delta).
    kv_contig_alloc: bool = True
    # background compaction: when the engine has no queued work and the
    # free-run fragmentation (pool.frag_ratio: 1 - largest_run/free)
    # exceeds this, the worst-fragmented resident sequence migrates
    # into a free run (engine/block_copy device copy + pool.relocate —
    # hash registrations follow the blocks). 0 disables. Skipped while
    # a replay recorder is attached (the copy is a device program the
    # follower streams don't carry).
    kv_defrag_threshold: float = 0.5
    # per-pass migration budget (one sequence, at most this many
    # blocks) — bounds the copy cost a pass can insert ahead of the
    # next admission
    kv_defrag_max_blocks: int = 64
    # KV-cache quantization: "none" | "int8" (per-token symmetric int8
    # pool + f32 scales — halves the decode KV read stream, the dominant
    # HBM term at seq >= ~1k). Current limits (refused loudly): no host
    # KV tier, no disagg handoff/onboarding (the bulk planes move raw
    # pool blocks and don't carry scale arrays yet).
    kv_quantization: str = "none"
    # weight-only quantization: "none" | "int8" | "int8-noembed" |
    # "int4" | "int4-noembed" (engine/quant.py — narrow weights with
    # dequant fused into the matmuls; int8 = per-output-channel scales,
    # halves the per-step weights-read floor; int4 = AWQ-style
    # per-(group-of-128, channel) scales on the dense matmuls + lm_head
    # with an int8 embed, quarters it). "-noembed" keeps the embedding
    # (and a tied lm head) in the load dtype — a quality/bandwidth middle
    # ground. The reference serves FP8/AWQ models via its engines; this
    # is the native analog.
    quantization: str = "none"
    seed: int = 0

    @staticmethod
    def auto_kv_block_size(model_cfg: "ModelConfig",
                           kv_quantization: str = "none") -> int:
        """Bring-up auto-selection for ``kv_block_size=0`` — the ONE home
        of the block-size policy, shared by EngineCore bring-up and
        bench.py so the served default and the benched default cannot
        drift. Small-C geometries (KVH·Dh <= 128 — e.g. the 70B TP-8
        shard's single KV head) are DMA-latency-bound at 16-token
        blocks: a 64-token block quadruples the per-DMA payload
        (round-5 probe: kernel 132 → 81 us/call, device step 29.3 →
        22.8 ms at the gate config, bs=16). int8 pools need 32 (the
        int8 sublane tile, attention.py pallas_supported); everything
        else keeps the 16-token default."""
        small_c = model_cfg.num_kv_heads * model_cfg.head_dim <= 128
        if small_c:
            return 64
        return 32 if kv_quantization == "int8" else 16

    def __post_init__(self) -> None:
        if self.kv_block_size < 0:
            raise ValueError("kv_block_size must be >= 0 (0 = auto-select "
                             "at engine bring-up)")
        if self.pp > 1:
            if self.decode_steps_per_dispatch <= 1:
                raise ValueError(
                    "pp > 1 requires decode_steps_per_dispatch > 1 (the "
                    "token-interleaved stage ring amortizes its "
                    "(pp-1)-tick fill/drain ramp over the K-step "
                    "dispatch; the single-step decode path has no pp "
                    "form)")
            if self.max_num_seqs % self.pp:
                raise ValueError(
                    f"pp={self.pp} must divide max_num_seqs="
                    f"{self.max_num_seqs} (one microbatch per stage)")
            if self.sp > 1 or self.dp > 1 or self.ep > 1:
                raise ValueError(
                    "pp composes with tp only (in-stage split-matmul); "
                    "sp/dp/ep must stay 1 on a pp engine")
            if self.spec_k > 0:
                raise NotImplementedError(
                    "speculative decoding on a pp engine is not "
                    "implemented (the verify program has no "
                    "token-interleaved form yet)")
            if self.quantization != "none" or self.kv_quantization != "none":
                raise NotImplementedError(
                    "pp with weight/KV quantization is not implemented "
                    "(QuantizedArray leaves under the stage shard_map "
                    "are unvalidated)")
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables speculation)")
        if not 0.0 <= self.kv_defrag_threshold <= 1.0:
            raise ValueError(
                "kv_defrag_threshold must be in [0, 1] (a frag_ratio "
                "bound; 0 disables the defrag pass)")
        if (self.kv_disk_blocks > 0) != bool(self.kv_disk_dir):
            raise ValueError(
                "the disk KV tier needs BOTH kv_disk_dir and "
                "kv_disk_blocks > 0 (set together, or neither)")
        if self.kv_disk_blocks > 0 and self.host_kv_blocks <= 0:
            raise ValueError(
                "the disk KV tier sits under the host tier (spill feeds "
                "on host evictions) — set host_kv_blocks > 0 too")
        if self.kv_remote_dir and self.kv_disk_blocks <= 0:
            raise ValueError(
                "the remote (G4) object tier sits under the disk tier "
                "(promotion feeds on disk evictions) — set kv_disk_dir/"
                "kv_disk_blocks too")
        if self.kv_remote_blocks > 0 and not self.kv_remote_dir:
            raise ValueError(
                "kv_remote_blocks needs kv_remote_dir (the object-store "
                "root); the peer fabric alone has no local capacity")
        if self.kv_remote_admission not in ("auto", "always", "never"):
            raise ValueError(
                "kv_remote_admission must be auto | always | never")
        if self.ragged_dispatch:
            if self.ragged_max_seq_rows <= 0:
                raise ValueError("ragged_max_seq_rows must be > 0")
            if self.ragged_max_tokens == 0:
                self.ragged_max_tokens = (self.max_num_seqs
                                          + 2 * self.ragged_max_seq_rows)
            if self.ragged_max_tokens < max(self.max_num_seqs + 1,
                                            self.ragged_max_seq_rows):
                raise ValueError(
                    f"ragged_max_tokens={self.ragged_max_tokens} must "
                    f"cover one decode row per slot plus prefill "
                    f"headroom (>= max_num_seqs+1 = "
                    f"{self.max_num_seqs + 1}) and at least one full "
                    f"per-sequence chunk (>= ragged_max_seq_rows = "
                    f"{self.ragged_max_seq_rows})")
            # composition matrix (docs/ragged_attention.md §composition):
            # ragged composes with speculative decoding (spec spans —
            # draft rows are just more span rows) and with
            # decode_dispatch_pipeline (the chained-sample merge); the
            # two survivors below are the full refusal set.
            if self.pp > 1:
                raise NotImplementedError(
                    "ragged dispatch on a pp engine is not implemented "
                    "(the ragged program has no token-interleaved stage "
                    "form yet). Ragged composes with tp, int8 KV, MLA, "
                    "sliding windows, speculative decoding (spec_k), "
                    "and decode_dispatch_pipeline — see docs/"
                    "ragged_attention.md §composition")
            if self.sp > 1:
                raise NotImplementedError(
                    "ragged dispatch with sequence-parallel prefill is "
                    "not implemented (long cold prompts would bypass "
                    "the ragged batch; run one or the other). Ragged "
                    "composes with tp, int8 KV, MLA, sliding windows, "
                    "speculative decoding (spec_k), and "
                    "decode_dispatch_pipeline — see docs/"
                    "ragged_attention.md §composition")
        if self.lane_prefill_max_tokens > 0 \
                and self.decode_steps_per_dispatch <= 1:
            raise ValueError(
                "lane_prefill_max_tokens requires decode_steps_per_dispatch"
                " > 1 (planned tokens feed the multi-step scan)")
        self.prefill_buckets = sorted(
            b for b in self.prefill_buckets if b <= self.max_model_len) or [
                self.max_model_len]
        if self.prefill_buckets[-1] < self.max_model_len:
            self.prefill_buckets.append(self.max_model_len)
        if self.pp > 1:
            bad = [b for b in self.prefill_buckets if b % self.pp]
            if bad or (self.prefill_chunk and self.prefill_chunk % self.pp):
                raise ValueError(
                    f"pp={self.pp} must divide every prefill bucket and "
                    f"prefill_chunk (one sub-chunk per stage): offending "
                    f"buckets={bad}, chunk={self.prefill_chunk}")

    @property
    def max_blocks_per_seq(self) -> int:
        return (self.max_model_len + self.kv_block_size - 1) // self.kv_block_size

    def bucket_for(self, length: int) -> int:
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(f"prompt length {length} exceeds max_model_len "
                         f"{self.max_model_len}")
