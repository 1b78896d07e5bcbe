"""Pipeline parallelism: layer-partitioned serving over a ``pp`` mesh axis.

Why PP exists here (VERDICT r4 item 6; reference analog: the vLLM
engines' ``pipeline_parallel_size=num_nodes``,
lib/llm/src/engines/vllm/subprocess.rs:41): tensor parallelism needs two
[B, D] all-reduces PER LAYER, which is only affordable over ICI — across
hosts on DCN (25 Gb/s) an 80-layer model would spend ~95 ms/step in
collectives (tools/bandwidth_model.py rates). Pipeline parallelism moves
ONE [B, D] activation per stage boundary per step — the only viable
cross-host axis, and the capacity enabler for checkpoints that exceed a
host's HBM (DeepSeek-V3 int8 ≈ 336 GB > any single v5e/v5p host).

PP is a THROUGHPUT axis, not just a capacity axis.

- **Token-interleaved decode** (`pp_decode_k_forward`): the decode batch
  B splits into ``pp`` microbatches of B/pp rows and round-robins them
  through the stage ring. At tick t, rank r runs step ``(t-r)//pp`` of
  microbatch ``(t-r) % pp`` through its local layer slice, then hands
  the [B/pp, D] activation to rank r+1 over one ppermute. The last rank
  additionally norms, projects, SAMPLES the microbatch's next token and
  sends the EMBEDDED next-step input back into the ring — so the
  sampled-token → next-step dependency rides the same boundary hop and
  every rank computes a LIVE microbatch every tick. A K-step dispatch
  runs ``K*pp + (pp-1)`` ticks: steady-state utilization
  K·pp/(K·pp+pp-1) → ~1 (a stage loop that is not microbatched
  keeps 1/pp), with the (pp-1)-tick fill/drain ramp amortized over the
  dispatch.
- **Microbatched prefill** (`pp_prefill_forward`): a padded [T] prompt
  chunk splits into pp sequential C=T/pp sub-chunks pipelined through
  the same schedule (chunk m at stage r on tick m+r, 2·pp-1 ticks) —
  chunked prefill FILLS the pipe instead of bubbling it. Each sub-chunk
  is exactly a ``_chunked_prefill`` continuation (start_pos + m·C
  against the KV earlier chunks already wrote), so the math matches the
  engine's sequential chunk walk.
- **tp×pp composition**: the stage ring composes with in-stage tensor
  parallelism for the split-matmul (unfused) llama dense path — layer
  stacks shard ("pp" on L, "tp" on the Megatron column/row axes), the
  KV pool shards ("pp" on L, "tp" on head lanes), and
  `llama._run_layers(reduce_axis="tp")` psums the row-parallel
  outputs inside the stage. Embed / final-norm / lm_head stay
  replicated (the last stage samples locally). ``fuse_stacked_matmuls``
  must stay OFF under ANY mesh — tp because the fused out axis cannot
  carry the column permutation, pp because the stage loop shards the
  unfused per-tensor layout (EngineCore gates on ``mesh is None``).

Exactness contract: per-microbatch KV scatters, positions, and sampling
keys are the SAME per-slot values the single-device decode_k scan uses
(make_slot_keys(seed, seeds[slot], steps0[slot]+k) — row-local, batch-
size-independent), so pp=k token streams are bit-exact vs single-device
(tests/test_pipeline_parallel.py asserts token equality over chained
dispatches, incl. through the EngineCore serving path and across a
preemption landing mid-stream).

Off-schedule (ramp) ticks compute garbage at full speed; their KV
scatters are masked to index NTOK, which is genuinely OUT OF BOUNDS and
dropped by mode="drop". (-1 would NOT work: advanced-index scatter
normalizes negatives first, so -1 silently overwrites the pool's LAST
row — round-5 review catch.)

Remaining limits (refused loudly by EngineCore, not silently wrong):
weight/KV quantization (QuantizedArray leaves under the stage shard_map
are unvalidated), MLA, speculative decoding (the verify program has no
interleaved form yet), sp composition, and sliding-window families (the
window flag depends on the GLOBAL layer index; statics are per-slice).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..engine.models import llama


# ------------------------------------------------------------------ schedule
def pp_dispatch_ticks(pp: int, K: int) -> int:
    """Ticks one K-step interleaved dispatch runs: K rounds of pp ticks
    plus the (pp-1)-tick fill/drain ramp."""
    return K * pp + (pp - 1)


def pp_dispatch_utilization(pp: int, K: int) -> float:
    """Fraction of a rank's ticks spent on a live microbatch: each rank
    idles exactly pp-1 ramp ticks per dispatch."""
    if pp <= 1:
        return 1.0
    return K * pp / pp_dispatch_ticks(pp, K)


def pp_bubble_fraction(pp: int, K: int) -> float:
    return 1.0 - pp_dispatch_utilization(pp, K)


def pp_split_config(statics, pp: int):
    """Per-stage statics: the local stack is num_layers/pp deep."""
    cfg = statics.cfg
    if cfg.num_layers % pp != 0:
        raise ValueError(
            f"pp={pp} must divide num_layers={cfg.num_layers}")
    if cfg.sliding_window is not None:
        raise NotImplementedError(
            "pp with sliding-window layer patterns is not implemented — "
            "the window flag depends on the GLOBAL layer index (statics "
            "are per-slice)")
    local_cfg = dataclasses.replace(cfg,
                                    num_layers=cfg.num_layers // pp)
    return dataclasses.replace(statics, cfg=local_cfg)


def _local_cfg_for(statics, pp: int, tp: int):
    """Per-rank model config: L/pp layers, and H/tp + KVH/tp heads when
    tensor parallelism runs inside the stage."""
    local_statics = pp_split_config(statics, pp)
    local_cfg = local_statics.cfg
    if tp > 1:
        cfg = statics.cfg
        if cfg.num_heads % tp or cfg.num_kv_heads % tp:
            raise ValueError(
                f"tp={tp} inside a pp stage must divide both head counts "
                f"(H={cfg.num_heads}, KVH={cfg.num_kv_heads})")
        if cfg.num_experts > 0:
            raise NotImplementedError(
                "tp×pp with MoE expert grids is not implemented (the "
                "in-stage reduce covers the dense split-matmul path)")
        local_cfg = dataclasses.replace(
            local_cfg, num_heads=cfg.num_heads // tp,
            num_kv_heads=cfg.num_kv_heads // tp)
    return dataclasses.replace(local_statics, cfg=local_cfg)


# ------------------------------------------------------- token interleaving
def pp_decode_k_forward(params, kv, tokens, positions, block_tables,
                        seeds, steps0, temperature, top_k, top_p,
                        planned, planned_mask, statics, mesh, K: int,
                        seed) -> Tuple[jax.Array, jax.Array, dict]:
    """Token-interleaved K-step decode over a pp(×tp) mesh — the SAME
    contract as the engine's fused decode_k scan: returns
    (toks [K, B] int32, logprobs [K, B] f32, kv), with per-(seed,
    key_step) sampling keys lockstep with single-device decode.

    Schedule (module docstring): microbatch m runs its k-th step through
    stage r at tick t = m + k·pp + r. The last stage samples and sends
    the embedded next-step input into the ring, so the token dependency
    crosses exactly one boundary per step — every rank is live every
    steady-state tick. ``planned``/``planned_mask`` [K, B] feed
    lane-prefill planned tokens exactly as the single-device scan does:
    step 0 inputs override at the rank-0 fresh embed, later steps at the
    last stage's next-token selection.
    """
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    from ..engine.sampling import make_slot_keys, sample_tokens

    cfg = statics.cfg
    pp = mesh.shape["pp"]
    tp = mesh.shape["tp"] if "tp" in mesh.axis_names else 1
    B = tokens.shape[0]
    if B % pp:
        raise ValueError(f"decode batch {B} must divide by pp={pp} "
                         f"(one microbatch per stage)")
    mb = B // pp
    local_statics = _local_cfg_for(statics, pp, tp)
    local_cfg = local_statics.cfg
    bsz = statics.block_size
    scale = llama._attn_scale(cfg)
    T_ticks = pp_dispatch_ticks(pp, K)
    ring = [(i, (i + 1) % pp) for i in range(pp)]

    stacks = {k: v for k, v in params.items() if k.startswith("layers.")}
    rest = {k: v for k, v in params.items()
            if not k.startswith("layers.")}
    specs = pp_param_pspecs(cfg, tp=tp)
    stack_specs = {k: specs.get(k, P("pp")) for k in stacks}
    rest_specs = {k: P() for k in rest}
    kv_specs = {k: v for k, v in pp_kv_pspecs(tp=tp).items() if k in kv}

    def stage_fn(stacks_l, rest_p, kv_l, tokens, positions, block_tables,
                 seeds, steps0, temperature, top_k, top_p, planned,
                 pmask):
        r = jax.lax.axis_index("pp")
        ntok = kv_l["k"].shape[1]
        num_blocks = ntok // bsz
        act_dtype = rest_p["final_norm"].dtype

        def mb_slice(a, m):
            return jax.lax.dynamic_slice_in_dim(a, m * mb, mb, axis=0)

        def tick(t, carry):
            x, kvk, kvv, toks_out, lps_out = carry
            km = t - r
            m = jnp.remainder(km, pp)
            k = jnp.floor_divide(km, pp)
            live = jnp.logical_and(km >= 0, km < K * pp)
            # a fresh microbatch enters the ring at rank 0, step 0: its
            # input token is host-fed (with the step-0 planned override,
            # exactly the single-device scan's where(pm, pt, tokens))
            tok0 = jnp.where(mb_slice(pmask[0], m),
                             mb_slice(planned[0], m), mb_slice(tokens, m))
            fresh = jnp.logical_and(r == 0,
                                    jnp.logical_and(live, k == 0))
            x = jnp.where(fresh, llama._embed(rest_p, tok0, cfg), x)

            pos_mb = mb_slice(positions, m) + k
            tables_mb = mb_slice(block_tables, m)
            slots = (tables_mb[jnp.arange(mb), pos_mb // bsz] * bsz
                     + pos_mb % bsz)
            slots = jnp.where(live, slots, ntok)   # ramp: OOB-dropped
            seq_lens = pos_mb + 1

            def attn(q, _k, _v, k_flat, v_flat, li, sliding):
                return llama.paged_attention(
                    q, k_flat, v_flat, tables_mb + li * num_blocks,
                    seq_lens, block_size=bsz, scale=scale,
                    impl=local_statics.attn_impl,
                    softcap=local_cfg.attn_logit_softcap,
                    kv_heads=local_cfg.num_kv_heads,
                    coalesce=local_statics.kv_coalesce)

            y, kv_new = llama._run_layers(
                stacks_l, {"k": kvk, "v": kvv}, x, pos_mb, slots,
                local_cfg, attn, final_norm=False,
                reduce_axis="tp" if tp > 1 else None)
            kvk, kvv = kv_new["k"], kv_new["v"]

            is_last = jnp.logical_and(r == pp - 1, live)
            kc = jnp.clip(k, 0, K - 1)

            def last_stage(y):
                # the finishing stage: norm + head + SAMPLE this
                # microbatch's step-k token, then send the EMBEDDED
                # next-step input into the ring (rank 0 consumes it next
                # tick). lax.cond keeps the head off the pp-1 other
                # ranks' ticks — it has no collectives, so the dynamic
                # branch is safe under shard_map.
                xn = llama.rms_norm(y, rest_p["final_norm"],
                                    cfg.rms_norm_eps, cfg.norm_plus_one)
                logits = llama._logits(rest_p, xn, cfg)
                keys = make_slot_keys(seed, mb_slice(seeds, m),
                                      mb_slice(steps0, m) + kc)
                toks, lps = sample_tokens(
                    logits, keys, mb_slice(temperature, m),
                    mb_slice(top_k, m), mb_slice(top_p, m))
                kn = jnp.clip(kc + 1, 0, K - 1)
                pl_row = jax.lax.dynamic_slice(planned, (kn, m * mb),
                                               (1, mb))[0]
                pm_row = jax.lax.dynamic_slice(pmask, (kn, m * mb),
                                               (1, mb))[0]
                tok_next = jnp.where(
                    jnp.logical_and(pm_row, kc + 1 < K), pl_row, toks)
                return toks, lps, llama._embed(rest_p, tok_next, cfg)

            def mid_stage(y):
                return (jnp.zeros((mb,), jnp.int32),
                        jnp.zeros((mb,), jnp.float32),
                        y.astype(act_dtype))

            toks_mb, lps_mb, x_send = jax.lax.cond(
                is_last, last_stage, mid_stage, y.astype(act_dtype))

            upd_t = jax.lax.dynamic_update_slice(
                toks_out, toks_mb[None], (kc, m * mb))
            upd_l = jax.lax.dynamic_update_slice(
                lps_out, lps_mb[None], (kc, m * mb))
            toks_out = jnp.where(is_last, upd_t, toks_out)
            lps_out = jnp.where(is_last, upd_l, lps_out)

            x = jax.lax.ppermute(x_send, "pp", ring)
            return (x, kvk, kvv, toks_out, lps_out)

        init = (jnp.zeros((mb, cfg.hidden_size), dtype=act_dtype),
                kv_l["k"], kv_l["v"],
                jnp.zeros((K, B), jnp.int32),
                jnp.zeros((K, B), jnp.float32))
        _, kvk, kvv, toks_out, lps_out = jax.lax.fori_loop(
            0, T_ticks, tick, init)
        # only rank pp-1 wrote its (live) rows; the rest hold zeros — the
        # pp psum replicates the harvest (tp ranks computed identical
        # replicated values, so no reduction over "tp")
        toks_out = jax.lax.psum(toks_out, "pp")
        lps_out = jax.lax.psum(lps_out, "pp")
        return toks_out, lps_out, {"k": kvk, "v": kvv}

    fn = shard_map(
        stage_fn, mesh=mesh,
        in_specs=(stack_specs, rest_specs, kv_specs,
                  P(), P(), P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), kv_specs),
        check_rep=False)
    return fn(stacks, rest, kv, tokens, positions, block_tables,
              seeds, steps0, temperature, top_k, top_p,
              planned, planned_mask)


def pp_prefill_forward(params, kv, tokens, block_table, start_pos,
                       true_len, statics, mesh
                       ) -> Tuple[jax.Array, dict]:
    """Microbatched single-sequence prefill over a pp(×tp) mesh — same
    contract as llama.prefill_forward (returns (logits_last [V], kv)).

    The padded [T] chunk splits into pp sequential C=T/pp sub-chunks;
    sub-chunk m runs stage r at tick m+r (2·pp-1 ticks total), so the
    pipe fills instead of every rank bubbling through the whole chunk.
    Each sub-chunk is mathematically the engine's ``_chunked_prefill``
    continuation: positions start_pos + m·C.., attention over the KV the
    earlier sub-chunks already wrote (chunk m-1 left rank r one tick
    before chunk m arrives — causality holds by the schedule). Pad
    positions scatter to the trash slot 0 exactly like prefill_forward;
    ramp ticks mask to the OOB NTOK drop."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    cfg = statics.cfg
    pp = mesh.shape["pp"]
    tp = mesh.shape["tp"] if "tp" in mesh.axis_names else 1
    T = tokens.shape[0]
    if T % pp:
        raise ValueError(f"prefill chunk length {T} must divide by "
                         f"pp={pp} (one sub-chunk per stage)")
    C = T // pp
    local_statics = _local_cfg_for(statics, pp, tp)
    local_cfg = local_statics.cfg
    bsz = statics.block_size
    scale = llama._attn_scale(cfg)
    ring = [(i, (i + 1) % pp) for i in range(pp)]
    use_flash = llama._prefill_flash_impl(local_statics)

    stacks = {k: v for k, v in params.items() if k.startswith("layers.")}
    rest = {k: v for k, v in params.items()
            if not k.startswith("layers.")}
    specs = pp_param_pspecs(cfg, tp=tp)
    stack_specs = {k: specs.get(k, P("pp")) for k in stacks}
    rest_specs = {k: P() for k in rest}
    kv_specs = {k: v for k, v in pp_kv_pspecs(tp=tp).items() if k in kv}

    def stage_fn(stacks_l, rest_p, kv_l, tokens, block_table, start_pos,
                 true_len):
        r = jax.lax.axis_index("pp")
        ntok = kv_l["k"].shape[1]
        act_dtype = rest_p["final_norm"].dtype

        def tick(t, carry):
            x, kvk, kvv, hbuf = carry
            m = t - r
            live = jnp.logical_and(m >= 0, m < pp)
            mc = jnp.clip(m, 0, pp - 1)
            toks_m = jax.lax.dynamic_slice_in_dim(tokens, mc * C, C)
            fresh = jnp.logical_and(r == 0, live)
            x = jnp.where(fresh, llama._embed(rest_p, toks_m, cfg), x)

            sp_m = start_pos + mc * C
            positions = sp_m + jnp.arange(C, dtype=jnp.int32)
            tl_m = jnp.clip(true_len - mc * C, 0, C)
            valid = jnp.arange(C, dtype=jnp.int32) < tl_m
            slots = jnp.where(
                valid,
                block_table[positions // bsz] * bsz + positions % bsz,
                0)
            slots = jnp.where(live, slots, ntok)   # ramp: OOB-dropped
            seq_len = sp_m + tl_m

            def attn(q, _k, _v, k_flat, v_flat, li, sliding):
                # the chunk attends the whole table (prefix + itself);
                # layer li's rows sit at offset li*NTOK in the local pool
                idx = (llama.flat_token_indices(
                    block_table[None, :], bsz)[0] + li * ntok)
                S = idx.shape[0]
                ks = jnp.take(k_flat, idx, axis=0).reshape(
                    S, local_cfg.num_kv_heads, cfg.head_dim)
                vs = jnp.take(v_flat, idx, axis=0).reshape(
                    S, local_cfg.num_kv_heads, cfg.head_dim)
                if use_flash:
                    return llama.flash_prefill(
                        q, ks, vs, scale=scale, start_pos=sp_m,
                        seq_len=seq_len, sliding=sliding,
                        window=cfg.sliding_window,
                        softcap=cfg.attn_logit_softcap or None,
                        interpret=(use_flash == "interpret"))
                g = local_cfg.num_heads // local_cfg.num_kv_heads
                qg = q.reshape(C, local_cfg.num_kv_heads, g, cfg.head_dim)
                scores = jnp.einsum("tkgd,skd->kgts", qg, ks).astype(
                    jnp.float32) * scale
                if cfg.attn_logit_softcap:
                    scores = llama._softcap(scores,
                                            cfg.attn_logit_softcap)
                kv_pos = jnp.arange(S, dtype=jnp.int32)
                mask = (kv_pos[None, :] <= positions[:, None]) & (
                    kv_pos[None, :] < seq_len)
                scores = jnp.where(mask[None, None, :, :], scores, -1e30)
                probs = jax.nn.softmax(scores, axis=-1).astype(vs.dtype)
                return jnp.einsum("kgts,skd->tkgd", probs, vs).reshape(
                    C, local_cfg.num_heads, cfg.head_dim)

            y, kv_new = llama._run_layers(
                stacks_l, {"k": kvk, "v": kvv}, x, positions, slots,
                local_cfg, attn, final_norm=False,
                reduce_axis="tp" if tp > 1 else None)
            y = y.astype(act_dtype)
            upd = jax.lax.dynamic_update_slice_in_dim(hbuf, y, mc * C,
                                                      axis=0)
            hbuf = jnp.where(jnp.logical_and(r == pp - 1, live),
                             upd, hbuf)
            x = jax.lax.ppermute(y, "pp", ring)
            return (x, kv_new["k"], kv_new["v"], hbuf)

        init = (jnp.zeros((C, cfg.hidden_size), dtype=act_dtype),
                kv_l["k"], kv_l["v"],
                jnp.zeros((T, cfg.hidden_size), dtype=act_dtype))
        _, kvk, kvv, hbuf = jax.lax.fori_loop(0, 2 * pp - 1, tick, init)
        hbuf = jax.lax.psum(hbuf, "pp")
        return hbuf, {"k": kvk, "v": kvv}

    fn = shard_map(
        stage_fn, mesh=mesh,
        in_specs=(stack_specs, rest_specs, kv_specs, P(), P(), P(), P()),
        out_specs=(P(), kv_specs),
        check_rep=False)
    hbuf, kv_new = fn(stacks, rest, kv, tokens, block_table,
                      jnp.asarray(start_pos, jnp.int32),
                      jnp.asarray(true_len, jnp.int32))
    last = hbuf[jnp.maximum(true_len - 1, 0)]
    last = llama.rms_norm(last, params["final_norm"], cfg.rms_norm_eps,
                          cfg.norm_plus_one)
    return llama._logits(params, last, cfg), kv_new


# -------------------------------------------------------------- placement
def pp_param_pspecs(cfg, tp: int = 1
                    ) -> Dict[str, "jax.sharding.PartitionSpec"]:
    """Layer stacks sharded on L over "pp" (composed with the Megatron
    "tp" column/row placement in-stage when tp > 1); embed / final_norm
    / lm_head stay REPLICATED — the last stage norms, projects and
    samples locally, so there is no vocab-sharded head to re-gather."""
    from jax.sharding import PartitionSpec as P

    from ..engine.models.llama import param_shapes
    from .sharding import param_pspecs
    base = param_pspecs(cfg) if tp > 1 else {}
    out = {}
    for k in param_shapes(cfg):
        if not k.startswith("layers."):
            out[k] = P()
            continue
        spec = base.get(k)
        if tp > 1 and spec is not None and len(spec) > 1:
            out[k] = P("pp", *tuple(spec)[1:])
        else:
            out[k] = P("pp")
    return out


def pp_kv_pspecs(tp: int = 1) -> Dict[str, "jax.sharding.PartitionSpec"]:
    """KV pools shard their leading L axis over "pp"; with in-stage tp
    the head-lane axis additionally shards over "tp" (each rank's pool
    rows carry only its own heads' lanes, like kv_pspecs)."""
    from jax.sharding import PartitionSpec as P
    if tp > 1:
        return {"k": P("pp", None, "tp"), "v": P("pp", None, "tp")}
    return {"k": P("pp"), "v": P("pp")}


def place_pp(params: dict, kv: dict, mesh, cfg) -> Tuple[dict, dict]:
    """Device-put params and KV pools under the pp(×tp) layout."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    tp = mesh.shape["tp"] if "tp" in mesh.axis_names else 1
    specs = pp_param_pspecs(cfg, tp=tp)
    params = {k: jax.device_put(v, NamedSharding(mesh,
                                                 specs.get(k, P())))
              for k, v in params.items()}
    kvs = pp_kv_pspecs(tp=tp)
    kv = {k: jax.device_put(v, NamedSharding(mesh, kvs[k]))
          for k, v in kv.items()}
    return params, kv


def make_pp_mesh(pp: int, tp: int = 1, devices=None):
    """Mesh with axes ("pp", "tp") — the stage ring crosses "pp" (the
    DCN-viable axis); in-stage collectives reduce over "tp" (ICI)."""
    import numpy as np
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    if pp * tp > len(devices):
        raise ValueError(f"pp*tp={pp * tp} > {len(devices)} devices")
    return Mesh(np.array(devices[:pp * tp]).reshape(pp, tp),
                ("pp", "tp"))
