#!/usr/bin/env python3
"""Quickest proof that the serving path starts on the chip.

    python chip_smoke.py            # one TPU chip, as the driver runs it
    python chip_smoke.py --chips 4  # only the tp=4 path and its tp=1 twin

One process (it holds the chip). It asserts a TPU before anything else,
then drives the normal entry point — the objects ``python -m
dynamo_tpu.launch.run in=http out=jax --model-path DIR --random-weights``
builds — at the published Llama-3.2-1B widths with seeded random weights:
HTTP requests over a real socket, checked for status, text, usage and
greedy repeatability; the compiled prefill/decode programs that served
are checked for their Pallas kernels (``tpu_custom_call``); the decode
and flash-prefill kernels are compared with the XLA paths on the chip.
A second phase repeats the serve with int8 weights (fused LM-head
kernel). Every failed check raises: there is no branch that logs and
carries on, and no CPU branch.

The last stdout line is ``{"ok": true, "device": {...}}`` and nothing
else goes in it; earlier lines are ``# key: value`` notes worth keeping.
"""

from __future__ import annotations

import argparse
import asyncio
import gc
import json
import os
import re
import shutil
import socket
import sys
import tempfile
import time

# Published Llama-3.2-1B config.json (meta-llama/Llama-3.2-1B): the widths
# dynamo_tpu.engine.config.bench_model_config("1b") carries. Only the
# tokenizer ids differ: the repo's fixture tokenizer stands in for the
# gated one, padded to the published vocabulary so every id has text.
MODEL_CONFIG = {
    "architectures": ["LlamaForCausalLM"],
    "model_type": "llama",
    "hidden_size": 2048,
    "intermediate_size": 8192,
    "num_hidden_layers": 16,
    "num_attention_heads": 32,
    "num_key_value_heads": 8,
    "head_dim": 64,
    "vocab_size": 128256,
    "max_position_embeddings": 131072,
    "rms_norm_eps": 1e-05,
    "rope_theta": 500000.0,
    "rope_scaling": {"factor": 32.0, "high_freq_factor": 4.0,
                     "low_freq_factor": 1.0,
                     "original_max_position_embeddings": 8192,
                     "rope_type": "llama3"},
    "tie_word_embeddings": True,
    "torch_dtype": "bfloat16",
}
MODEL_NAME = "llama-3.2-1b-random"
# flags beyond ``in=http out=jax --model-path DIR --random-weights``
# (the CPU rehearsal shrinks the pool here, never the chip run)
EXTRA_FLAGS: list = []
LONG_TOKENS = 64          # one request long enough for decode to chain
# kernel-vs-XLA comparison sizes (head widths come from MODEL_CONFIG)
PARITY = {"B": 8, "M": 32, "blocks": 320, "block_size": 16,
          "T": 512, "valid": 389}
PALLAS_IMPL = "pallas"    # the CPU rehearsal says "pallas_interpret"
# bf16 attention outputs are O(1) averages of unit-variance values: eight
# mantissa bits and f32 accumulation leave ~1e-2 between two schedules
KERNEL_ATOL = 3e-2
# tp=4 changes every matmul's reduction order (psum of 4 partials): the
# first-step logits of the 16-layer bf16 model differed by 0.085 of their
# own spread on the chip (max abs 0.0107 over a std of 0.126, PR 25). A
# wrong reduction lands far outside: with the attention-output reduction
# doubled or left out, the same logits (same seed, same widths, computed
# on the CPU) move by 2.8 and 6.4 spreads, and by 0.87 when only the
# last of the 16 layers is broken; tests/test_bring_up.py trips this
# check both ways on four virtual devices
TP_LOGITS_RTOL = 2e-1


def note(key: str, value) -> None:
    print(f"# {key}: {value}", flush=True)


def require_tpu():
    """The platform check, before anything else touches the repo."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found platform {devices[0].platform!r}, "
            "not a TPU; nothing was run")
    return devices


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")


# ---------------------------------------------------------------- set-up

def write_model_dir(path: str) -> str:
    """config.json at the published widths + the fixture tokenizer, from
    files in the repo only."""
    from tests.fixtures import CHAT_TEMPLATE, build_tiny_tokenizer
    os.makedirs(path, exist_ok=True)
    tok = build_tiny_tokenizer()
    tok.add_tokens([f"<t{i}>" for i in range(tok.get_vocab_size(),
                                             MODEL_CONFIG["vocab_size"])])
    check(tok.get_vocab_size() == MODEL_CONFIG["vocab_size"],
          "tokenizer padded to the model vocabulary")
    tok.save(os.path.join(path, "tokenizer.json"))
    cfg = dict(MODEL_CONFIG, eos_token_id=tok.token_to_id("<|endoftext|>"),
               bos_token_id=None)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"chat_template": CHAT_TEMPLATE,
                   "eos_token": "<|endoftext|>"}, f)
    return path


def report_environment(devices) -> None:
    import importlib.metadata as md
    import jax
    import jaxlib
    note("python", sys.version.split()[0])
    note("jax", jax.__version__)
    note("jaxlib", jaxlib.__version__)
    note("libtpu", md.version("libtpu"))
    note("device_kind", devices[0].device_kind)
    note("device_count", len(devices))
    # a failed native build raises here (utils/native.py): all four load
    from dynamo_tpu.llm.kv.native_pool import load_native_pool_lib
    from dynamo_tpu.llm.kv_router.c_abi import load_abi
    from dynamo_tpu.runtime.native_tcp import load_data_plane_lib
    from dynamo_tpu.utils import native
    libs = {"kv_reuse_pool": load_native_pool_lib(),
            "data_plane": load_data_plane_lib(),
            "dynkvabi": load_abi(),
            "dynkv": native.load("dynkv", ["kv_radix_index.cpp"])}
    note("native_libs_loaded",
         ", ".join(os.path.basename(lib._name) for lib in libs.values()))
    # what the serve supervisor would count without touching JAX
    from dynamo_tpu.sdk.allocator import TpuAllocator
    note("allocator_chip_count_from_device_nodes", TpuAllocator().total)


class CacheWatch:
    """Cold or warm, as JAX itself reports it: persistent-cache hits and
    misses of this process, and the entries found at start."""

    def __init__(self, cache_dir: str):
        from jax import monitoring
        self.dir = cache_dir
        self.entries_at_start = (len(os.listdir(cache_dir))
                                 if os.path.isdir(cache_dir) else 0)
        self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self, tag: str) -> None:
        """Counts are cumulative over the process; the state is what the
        hits say, not what the directory held."""
        state = ("cold" if not self.hits else
                 "warm" if not self.misses else "partly warm")
        note(f"compile_cache[{tag}]",
             f"dir={self.dir} state={state} "
             f"entries_at_start={self.entries_at_start} "
             f"hits={self.hits} misses={self.misses}")


# ------------------------------------------------- kernels vs XLA on chip

def kernel_parity() -> None:
    """Pallas decode and flash-prefill outputs against attn_impl="xla" at
    the 1B widths, on the chip, within KERNEL_ATOL."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dynamo_tpu.engine import attention as A
    H = MODEL_CONFIG["num_attention_heads"]
    KVH = MODEL_CONFIG["num_key_value_heads"]
    Dh = MODEL_CONFIG["head_dim"]
    bs, nblk, B, M = (PARITY["block_size"], PARITY["blocks"], PARITY["B"],
                      PARITY["M"])
    interpret = PALLAS_IMPL == "pallas_interpret"
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, H, Dh), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (nblk * bs, KVH * Dh), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (nblk * bs, KVH * Dh), jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, nblk))[:B * M]
                         .reshape(B, M).astype(np.int32))
    lens = jnp.asarray(rng.integers(1, M * bs, size=B).astype(np.int32))
    outs = {impl: jax.jit(lambda q, k, v, t, n, impl=impl:
                          A.paged_attention(q, k, v, t, n, block_size=bs,
                                            scale=Dh ** -0.5, impl=impl))(
                              q, kc, vc, tables, lens)
            for impl in (PALLAS_IMPL, "xla")}
    err = float(jnp.max(jnp.abs(outs[PALLAS_IMPL].astype(jnp.float32)
                                - outs["xla"].astype(jnp.float32))))
    note("decode_kernel_vs_xla_max_abs_err", f"{err:.4g} (atol "
         f"{KERNEL_ATOL}, B={B} H={H} KVH={KVH} Dh={Dh} block={bs})")
    check(err <= KERNEL_ATOL
          and bool(jnp.isfinite(outs[PALLAS_IMPL]).all()),
          "Pallas decode agrees with XLA")

    T, n_valid = PARITY["T"], PARITY["valid"]
    qp = jax.random.normal(ks[3], (T, H, Dh), jnp.bfloat16)
    kp = jax.random.normal(ks[4], (T, KVH, Dh), jnp.bfloat16)
    vp = jax.random.normal(ks[5], (T, KVH, Dh), jnp.bfloat16)
    flash = jax.jit(lambda q, k, v: A.flash_prefill(
        q, k, v, scale=Dh ** -0.5, start_pos=0, seq_len=n_valid,
        interpret=interpret))(qp, kp, vp)
    dense = jax.jit(lambda q, k, v: A.causal_attention(
        q, k, v, scale=Dh ** -0.5, length=n_valid))(qp, kp, vp)
    err = float(jnp.max(jnp.abs(flash[:n_valid].astype(jnp.float32)
                                - dense[:n_valid].astype(jnp.float32))))
    note("flash_prefill_vs_xla_max_abs_err", f"{err:.4g} (atol "
         f"{KERNEL_ATOL}, T={T} valid={n_valid})")
    check(err <= KERNEL_ATOL and bool(jnp.isfinite(flash[:n_valid]).all()),
          "Pallas flash prefill agrees with XLA")


# --------------------------------------------------------------- serving

class ProgramRecorder:
    """Stands where one of the engine's jitted steps stands and notes the
    argument shapes it is called with, so the programs that SERVED can be
    re-lowered afterwards and read (``compiled.as_text()``)."""

    def __init__(self, jitted):
        self.jitted = jitted
        self.calls = 0
        self.signatures: dict = {}

    def __call__(self, *args):
        import jax
        self.calls += 1
        key = tuple((x.shape, str(x.dtype)) for x in jax.tree.leaves(args))
        if key not in self.signatures:
            # host-fed inputs are uncommitted (they follow the placed ones)
            self.signatures[key] = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    x.shape, x.dtype,
                    sharding=x.sharding if x.committed else None), args)
        return self.jitted(*args)

    def texts(self) -> list:
        return [self.jitted.lower(*spec).compile().as_text()
                for spec in self.signatures.values()]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The launcher's own objects, inside this process."""

    def __init__(self, model_dir: str, flags: list):
        self.model_dir = model_dir
        self.flags = flags
        self.port = free_port()

    async def __aenter__(self):
        from dynamo_tpu.launch import run
        self.args = run.build_parser().parse_args(
            ["in=http", "out=jax", "--model-path", self.model_dir,
             "--model-name", MODEL_NAME, "--random-weights",
             "--http-host", "127.0.0.1", "--http-port", str(self.port),
             *EXTRA_FLAGS, *self.flags])
        src, out = run.parse_io(self.args.io)
        check((src, out) == ("http", "jax"), "in=http out=jax")
        t0 = time.monotonic()
        self.runtime = await run.make_runtime(self.args)
        self.engine, self.mdc, self.core = await run.build_engine(
            self.args, out, self.runtime)
        self.build_s = time.monotonic() - t0
        core = self.core
        self.programs = {}
        for name in ("_prefill_jit", "_decode_jit", "_decode_k_jit"):
            if getattr(core, name) is not None:
                self.programs[name] = ProgramRecorder(getattr(core, name))
                setattr(core, name, self.programs[name])
        pipeline = run.link_pipeline(self.engine, self.mdc)
        self.http_task = asyncio.create_task(
            run.run_http(self.args, pipeline, core))
        import aiohttp
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=900))
        self.base = f"http://127.0.0.1:{self.port}"
        for _ in range(200):
            if self.http_task.done():
                self.http_task.result()       # surfaces the bind error
            try:
                async with self.session.get(self.base + "/health") as r:
                    if r.status == 200:
                        break
            except aiohttp.ClientConnectionError:
                await asyncio.sleep(0.05)
        else:
            raise AssertionError("HTTP service never answered /health")
        return self

    async def __aexit__(self, *exc):
        await self.session.close()
        self.http_task.cancel()
        await asyncio.gather(self.http_task, return_exceptions=True)
        await self.core.stop()
        await self.runtime.shutdown()

    async def post(self, path: str, body: dict) -> dict:
        async with self.session.post(self.base + path, json=body) as r:
            text = await r.text()
            check(r.status == 200, f"POST {path} → {r.status}: {text[:300]}")
            return json.loads(text)

    async def post_sse(self, path: str, body: dict) -> list:
        chunks = []
        async with self.session.post(self.base + path, json=body) as r:
            check(r.status == 200, f"POST {path} (stream) → {r.status}")
            done = False
            async for raw in r.content:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[len("data:"):].strip()
                if data == "[DONE]":
                    done = True
                    break
                chunks.append(json.loads(data))
        check(done, "SSE stream ended with [DONE]")
        return chunks


def _chat(prompt: str, max_tokens: int, **extra) -> dict:
    return {"model": MODEL_NAME, "max_tokens": max_tokens,
            "temperature": 0, "nvext": {"ignore_eos": True},
            "messages": [{"role": "user", "content": prompt}], **extra}


def _completion(prompt: str, max_tokens: int) -> dict:
    return {"model": MODEL_NAME, "max_tokens": max_tokens,
            "temperature": 0, "nvext": {"ignore_eos": True},
            "prompt": prompt}


def _check_usage(resp: dict, max_tokens: int, what: str) -> int:
    usage = resp.get("usage") or {}
    check(usage.get("prompt_tokens", 0) > 0, f"{what}: prompt_tokens")
    check(usage.get("completion_tokens") == max_tokens,
          f"{what}: completion_tokens {usage.get('completion_tokens')} "
          f"!= {max_tokens}")
    return usage["completion_tokens"]


async def drive_requests(srv: Server, full: bool) -> int:
    """→ tokens generated. ``full``: the whole request set (bf16 phase);
    otherwise one chat and the long completion."""
    generated = 0
    t0 = time.monotonic()
    first = await srv.post("/v1/chat/completions", _chat("hi", 16))
    first_s = time.monotonic() - t0
    text = first["choices"][0]["message"]["content"]
    check(bool(text), "chat: non-empty text")
    generated += _check_usage(first, 16, "chat")
    # shorter than one KV block: nothing of it is cached, so the repeat
    # runs the identical programs on identical inputs
    check(first["usage"]["prompt_tokens"] < srv.core.cfg.kv_block_size,
          "repeat prompt shorter than one KV block")
    t0 = time.monotonic()
    again = await srv.post("/v1/chat/completions", _chat("hi", 16))
    steady_s = time.monotonic() - t0
    check(again["choices"][0]["message"]["content"] == text,
          "greedy request repeated gives the same tokens")
    generated += _check_usage(again, 16, "chat repeat")
    note("seconds_to_ready",
         f"engine_build={srv.build_s:.1f} first_request(compile)="
         f"{first_s:.1f} total={srv.build_s + first_s:.1f}; "
         f"same request again={steady_s:.3f}")

    long = await srv.post("/v1/completions", _completion(
        "the quick brown fox jumps over the lazy dog", LONG_TOKENS))
    check(bool(long["choices"][0]["text"]), "completion: non-empty text")
    generated += _check_usage(long, LONG_TOKENS, "long completion")
    if not full:
        return generated

    chunks = await srv.post_sse("/v1/chat/completions", _chat(
        "tokens and more tokens", 16, stream=True,
        stream_options={"include_usage": True}))
    streamed = "".join((c["choices"][0].get("delta") or {}).get("content")
                       or "" for c in chunks if c.get("choices"))
    check(bool(streamed), "streamed chat: non-empty text")
    usage = [c["usage"] for c in chunks if c.get("usage")]
    check(bool(usage) and usage[-1]["completion_tokens"] == 16,
          "streamed chat: usage chunk with completion_tokens")
    generated += 16

    pair = await asyncio.gather(
        srv.post("/v1/completions", _completion("hello world", 24)),
        srv.post("/v1/completions",
                 _completion("paged attention on tpu hardware", 24)))
    for i, resp in enumerate(pair):
        check(bool(resp["choices"][0]["text"]), f"concurrent {i}: text")
        generated += _check_usage(resp, 24, f"concurrent {i}")
    return generated


def kernels_in(text: str) -> list:
    """op_name of every Pallas kernel (``tpu_custom_call``) in a compiled
    program's text."""
    return re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"',
        text)


def impls_in(kernels: list) -> str:
    """The impl a program resolved to, read from the kernels its compiled
    text holds and from nothing the smoke works out for itself: the fused
    LM head lowers under its own jit name; any other Pallas call in the
    engine's step programs is the attention kernel."""
    head = any("lm_head_int8" in k for k in kernels)
    attn = any("lm_head_int8" not in k for k in kernels)
    return (f"attn={'pallas' if attn else 'xla'} "
            f"lm_head={'pallas-int8' if head else 'xla'}")


def all_reduces_in(text: str) -> int:
    return text.count("all-reduce(") + text.count("all-reduce-start(")


def check_programs(srv: Server, expect: dict) -> dict:
    """The programs that served, re-lowered at the shapes they were
    called with: each must carry its Pallas kernels. → {name: texts}."""
    texts = {name: rec.texts() for name, rec in srv.programs.items()
             if rec.calls}
    for name, rec in srv.programs.items():
        for text in texts.get(name, ()):
            kernels = kernels_in(text)
            note(f"program[{name}]", f"calls={rec.calls} "
                 f"impl: {impls_in(kernels)} "
                 f"tpu_custom_call={len(kernels)} all-reduce="
                 f"{all_reduces_in(text)} kernels={sorted(set(kernels))}")
            check(bool(kernels), f"{name} contains tpu_custom_call")
            for needle in expect.get(name, ()):
                check(any(needle in k for k in kernels),
                      f"{name} carries the {needle} kernel")
    check("_prefill_jit" in texts and texts.keys() & {"_decode_jit",
                                                     "_decode_k_jit"},
          f"prefill and decode programs served (got {sorted(texts)})")
    return texts


def engine_settings(core) -> str:
    """Settings the engine was built with (what each program resolved
    to is read from its compiled text, in check_programs)."""
    return (f"attn_impl={core.statics.attn_impl} "
            f"kv_block={core.cfg.kv_block_size} "
            f"K={core.cfg.decode_steps_per_dispatch}")


async def serve_phase(tag: str, model_dir: str, flags: list, full: bool,
                      expect: dict, cache: CacheWatch) -> None:
    t0 = time.monotonic()
    async with Server(model_dir, flags) as srv:
        note(f"phase[{tag}]", f"flags={flags or '(defaults)'} "
             f"{engine_settings(srv.core)}")
        generated = await drive_requests(srv, full)
        check_programs(srv, expect)
    note(f"phase[{tag}]", f"tokens_generated={generated} "
         f"seconds={time.monotonic() - t0:.1f}")
    cache.report(tag)
    del srv
    gc.collect()


async def one_chip(model_dir: str, cache: CacheWatch) -> None:
    kernel_parity()
    # attention kernels carry no name of their own in the program text
    # (…/pallas_call): their presence is the tpu_custom_call count
    await serve_phase("bf16", model_dir, [], True, {}, cache)
    # int8 weights: the fused LM-head kernel, now without a self-test to
    # hide behind; K=8 so the decode program serves with its scan as well
    # as without (one step per dispatch, the phase above)
    await serve_phase(
        "int8", model_dir,
        ["--quantization", "int8", "--decode-steps-per-dispatch", "8"],
        False, {"_prefill_jit": ["lm_head_int8"],
                "_decode_k_jit": ["lm_head_int8"]}, cache)


# ------------------------------------------------------------ four chips

def _bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def _first_step_logits(core, prompt_ids: list):
    """Logits of the first generated position, through the model's own
    prefill forward on the engine's placed parameters (pool not donated:
    the serving state is left as it was)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    bucket = core.cfg.prefill_buckets[0]
    tokens = np.zeros((bucket,), np.int32)
    tokens[:len(prompt_ids)] = prompt_ids
    table = np.arange(1, core.M + 1, dtype=np.int32)
    fwd = jax.jit(lambda p, kv, t, bt, n: core.model_mod.prefill_forward(
        p, kv, t, bt, jnp.int32(0), n, core.statics)[0])
    logits = fwd(core.params, core.kv, jnp.asarray(tokens),
                 jnp.asarray(table), jnp.int32(len(prompt_ids)))
    return np.asarray(logits, np.float32)


async def four_chips(model_dir: str, cache: CacheWatch, devices) -> None:
    """Only the path across chips: one process, make_mesh(tp=4) over the
    four devices through the same entry point, against tp=1 on one of
    them from the same seed."""
    import numpy as np
    check(len(devices) == 4, f"--chips 4 needs 4 devices, found "
                             f"{len(devices)}")
    prompt = "the quick brown fox jumps over the lazy dog"
    results = {}
    for tag, flags in (("tp1", []), ("tp4", ["--tp", "4"])):
        base = _bytes_in_use(devices)    # whatever the last phase left
        async with Server(model_dir, flags) as srv:
            core = srv.core
            note(f"phase[{tag}]", f"flags={flags or '(defaults)'} "
                 f"{engine_settings(core)} mesh="
                 f"{dict(core.mesh.shape) if core.mesh else None}")
            if tag == "tp4":
                note("fused_lm_head_under_tp",
                     f"lm_head_pallas={core.model_cfg.lm_head_pallas} "
                     "(core.py turns the fused head off when tp>1; it "
                     "only matters with quantized weights)")
            in_use = [b - b0 for b, b0 in
                      zip(_bytes_in_use(devices), base)]
            note(f"bytes_in_use[{tag}]", f"{in_use} (over {base} before)")
            resp = await srv.post("/v1/completions",
                                  _completion(prompt, 32))
            _check_usage(resp, 32, f"{tag} completion")
            prompt_ids = srv.mdc.tokenizer().encode(prompt).ids
            texts = check_programs(srv, {})
            results[tag] = {
                "text": resp["choices"][0]["text"],
                "logits": _first_step_logits(core, prompt_ids),
                "in_use": in_use,
                "all_reduce": sum(all_reduces_in(t)
                                  for name, ts in texts.items()
                                  if name != "_prefill_jit" for t in ts),
            }
        cache.report(tag)
        del srv, core
        gc.collect()

    one, four = results["tp1"], results["tp4"]
    check(np.isfinite(four["logits"]).all(), "tp=4 logits finite")
    spread = float(np.std(one["logits"]))
    err = float(np.max(np.abs(one["logits"] - four["logits"])))
    note("tp4_vs_tp1_first_step_logits",
         f"max_abs_err={err:.4g} logits_std={spread:.4g} "
         f"rel={err / spread:.4g} (rtol {TP_LOGITS_RTOL}) "
         f"argmax_equal={int(one['logits'].argmax() == four['logits'].argmax())}")
    check(err <= TP_LOGITS_RTOL * spread,
          "tp=4 first-step logits agree with tp=1")
    a = re.findall(r"<t\d+>|.", one["text"], re.S)
    b = re.findall(r"<t\d+>|.", four["text"], re.S)
    shared = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y),
                  min(len(a), len(b)))
    note("tp4_vs_tp1_greedy_shared_prefix",
         f"{shared} of {min(len(a), len(b))} text units")
    # really spread: every device holds about a quarter of what the
    # single chip held (weights + KV pool; norms replicate)
    single = max(one["in_use"])
    check(single > 0, "tp=1 engine occupies device memory")
    shares = [b / single for b in four["in_use"]]
    note("tp4_share_of_single_chip_bytes_per_device",
         [round(s, 3) for s in shares])
    check(all(0.2 <= s <= 0.35 for s in shares),
          f"every device holds about a quarter (shares {shares})")
    check(four["all_reduce"] > 0, "all-reduce in the tp=4 decode program")
    check(one["all_reduce"] == 0, "no all-reduce in the tp=1 program")


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tp=4 path and its tp=1 twin")
    opts = ap.parse_args(argv)

    devices = require_tpu()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    cache = CacheWatch(enable_compile_cache())
    from dynamo_tpu.runtime.log import setup_logging
    setup_logging(None)
    t0 = time.monotonic()
    report_environment(devices)
    model_dir = tempfile.mkdtemp(prefix="chip_smoke_model_")
    try:
        write_model_dir(model_dir)
        if opts.chips == 4:
            asyncio.run(four_chips(model_dir, cache, devices))
        else:
            asyncio.run(one_chip(model_dir, cache))
    finally:
        shutil.rmtree(model_dir, ignore_errors=True)
    note("total_seconds", f"{time.monotonic() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
