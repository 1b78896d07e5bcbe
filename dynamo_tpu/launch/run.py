"""The single-binary launcher: ``python -m dynamo_tpu.launch.run in=<src>
out=<engine> [flags]``.

Reference: launch/dynamo-run (src/opt.rs:23-130 input/output matrix,
src/flags.rs:22-158 flag set, src/input/common.rs:35-92 pipeline link,
src/input/endpoint.rs:34-115 worker registration).

Inputs:  http | text | stdin | batch:FILE.jsonl | dyn://ns/comp/ep | none
Outputs: jax | echo_core | echo_full | dyn://ns/comp/ep

The canonical local pipeline for core engines (jax/echo_core) is
preprocessor → backend(detokenizer) → engine, exactly the reference's
6-stage link (SURVEY.md §3.1). ``out=dyn://`` makes this process a frontend
routing to remote workers; ``in=dyn://`` makes it a worker serving its
pipeline on the distributed runtime. Disaggregation: ``--remote-prefill``
turns the worker into a disagg decode worker; ``--is-prefill-worker`` (with
``in=none``) runs the prefill side pulling the shared queue."""

from __future__ import annotations

import argparse
import asyncio
import json
import logging
import os
import sys
from typing import Tuple

logger = logging.getLogger("dynamo_tpu.launch")


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dynamo-tpu-run",
        description="TPU-native LLM serving launcher (in=SRC out=ENGINE)")
    p.add_argument("io", nargs="*", metavar="in=|out=",
                   help="in=http|text|stdin|batch:F|dyn://ns/c/e|none "
                        "out=jax|echo_core|echo_full|pystr:F|pytok:F|"
                        "dyn://ns/c/e")
    p.add_argument("--model-path", help="HF-style model dir (config.json, "
                                        "tokenizer.json, safetensors)")
    p.add_argument("--model-name", help="served model name "
                                        "(default: basename of model path)")
    p.add_argument("--http-port", type=int, default=8080)
    p.add_argument("--http-host", default="0.0.0.0")
    p.add_argument("--namespace", default="dynamo")
    p.add_argument("--runtime-server",
                   help="discovery daemon host:port (default: in-process "
                        "runtime — single-process deployments)")
    p.add_argument("--advertise-host",
                   help="address other hosts can dial back (DCN)")
    # engine knobs (flags.rs analogs)
    p.add_argument("--max-model-len", type=int, default=4096)
    p.add_argument("--kv-block-size", type=int, default=0,
                   help="paged-KV block size; 0 (default) auto-selects "
                        "from the model geometry at bring-up "
                        "(EngineConfig.auto_kv_block_size: 64 for "
                        "small-C KVH*Dh<=128 geometries, 32 for int8 "
                        "KV pools, else 16)")
    p.add_argument("--prefill-chunk", type=int, default=0,
                   help="split prompt prefill into fixed-size chunk "
                        "dispatches (0 = whole-prompt)")
    p.add_argument("--prefill-buckets", default="",
                   help="comma-separated prompt lengths the prefill "
                        "program is compiled for (a prompt pads to the "
                        "next one; --max-model-len is always the last); "
                        "empty = the engine's default list")
    p.add_argument("--decode-steps-per-dispatch", type=int, default=1,
                   help="fuse K decode steps per XLA dispatch (amortizes "
                        "device→host token-harvest latency; EOS/cancel "
                        "react at K-step granularity)")
    p.add_argument("--lane-prefill-max-tokens", type=int, default=0,
                   help="admissions with <= this many un-cached prompt "
                        "tokens ride the decode batch as planned inputs "
                        "when the engine is busy (continuous batching; "
                        "0 disables, needs K>1)")
    p.add_argument("--ragged", action="store_true",
                   help="unified ragged dispatch (engine/ragged.py): "
                        "ONE compiled program serves mixed prefill+"
                        "decode batches — admissions ride the batch as "
                        "prefill lanes, continuous batching becomes "
                        "the only serving code path "
                        "(docs/ragged_attention.md)")
    p.add_argument("--ragged-max-tokens", type=int, default=0,
                   help="token capacity of one ragged dispatch (0 = "
                        "auto: max_num_seqs + 2*ragged-max-seq-rows)")
    p.add_argument("--ragged-max-seq-rows", type=int, default=64,
                   help="per-sequence row budget per ragged dispatch "
                        "(longer prompts stream across dispatches)")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative decoding: max prompt-lookup draft "
                        "tokens verified per step (engine/spec/; 0 "
                        "disables; per-request override via "
                        "nvext.speculation, live retune via llmctl "
                        "spec set-k)")
    p.add_argument("--decode-dispatch-pipeline", action="store_true",
                   help="K>1 / --ragged: overlap each dispatch's token "
                        "harvest with the next dispatch (finish reaction "
                        "widens to <=2K-1 steps); one step per dispatch "
                        "always does")
    p.add_argument("--num-kv-blocks", type=int, default=2048)
    p.add_argument("--max-num-seqs", type=int, default=8)
    p.add_argument("--host-kv-blocks", type=int, default=0,
                   help="host (TPU-VM DRAM) KV offload tier size")
    p.add_argument("--kv-disk-dir", default="",
                   help="persistent disk (G3) KV tier directory "
                        "(llm/kv/diskstore.py): host-tier evictions "
                        "spill here and a restarted engine pointed at "
                        "the same dir warm-starts from the previous "
                        "run's cache; needs --kv-disk-blocks and "
                        "--host-kv-blocks")
    p.add_argument("--kv-disk-blocks", type=int, default=0,
                   help="disk KV tier capacity in blocks (0 = off)")
    p.add_argument("--kv-remote-dir", default="",
                   help="remote (G4) object-store root (llm/kv/"
                        "remotestore.py — a mounted bucket/NFS export "
                        "shared across the fleet): disk-tier evictions "
                        "promote here write-behind and any worker "
                        "pointed at the same root reuses them; needs "
                        "the disk tier")
    p.add_argument("--kv-remote-blocks", type=int, default=0,
                   help="object tier capacity in blocks (0 = unbounded)")
    p.add_argument("--tenancy", action="store_true",
                   help="multi-tenant serving plane (llm/tenancy.py): "
                        "per-tenant KV block accounting + quota-"
                        "preferred eviction across the device/host/"
                        "disk/remote tiers, per-tenant nv_llm_tenant_* "
                        "stats, and the tenant/control/{ns} policy "
                        "watch (llmctl tenant {set-weight,set-quota})")
    p.add_argument("--kv-fabric", action="store_true",
                   help="join the fleet KV fabric (llm/kv/fabric.py): "
                        "serve this worker's disk/host KV to peers over "
                        "a kv_fabric endpoint and fetch peers' prefixes "
                        "instead of recomputing them, behind a "
                        "latency-aware admission gate")
    p.add_argument("--kv-remote-admission",
                   choices=["auto", "always", "never"], default="auto",
                   help="remote-hit admission: auto = promote only when "
                        "the modeled fetch beats the modeled recompute")
    p.add_argument("--no-prefix-reuse", action="store_true")
    p.add_argument("--kv-quantization",
                   choices=["none", "int8"], default="none",
                   help="KV-cache quantization (int8: per-token in-row "
                        "scales, 1.6-1.8x KV-byte cut, needs "
                        "--kv-block-size %% 32 == 0; the long-context "
                        "capacity lever)")
    p.add_argument("--quantization",
                   choices=["none", "int8", "int8-noembed",
                            "int4", "int4-noembed"],
                   default="none",
                   help="weight-only quantization (int8: per-channel "
                        "scales; int4: per-group-of-128 scales on dense "
                        "matmuls + lm_head, int8 embed; dequant fused "
                        "into matmuls; -noembed keeps the embedding "
                        "full-precision)")
    p.add_argument("--random-weights", action="store_true",
                   help="skip checkpoint load (benchmarks/smoke)")
    # parallelism (tensor-parallel-size analog + our axes)
    p.add_argument("--tensor-parallel-size", "--tp", type=int, default=1,
                   dest="tp")
    p.add_argument("--sequence-parallel-size", "--sp", type=int, default=1,
                   dest="sp")
    p.add_argument("--data-parallel-size", "--dp", type=int, default=1,
                   dest="dp")
    p.add_argument("--expert-parallel-size", "--ep", type=int, default=1,
                   dest="ep")
    p.add_argument("--pipeline-parallel-size", "--pp", type=int, default=1,
                   dest="pp",
                   help="pipeline-parallel stages (token-interleaved "
                        "stage ring, parallel/pipeline_parallel.py): "
                        "layer stacks + KV pool shard over pp; the "
                        "decode batch round-robins pp microbatches so "
                        "every stage computes each tick. The DCN-viable "
                        "cross-host axis. Composes with --tp only; "
                        "needs --decode-steps-per-dispatch > 1 and "
                        "--max-num-seqs divisible by pp")
    # multi-node bootstrap (reference MultiNodeConfig, engines.rs:33-50):
    # every host runs the same command with its own --node-rank; rank 0's
    # address is the coordinator
    p.add_argument("--num-nodes", type=int, default=1)
    p.add_argument("--node-rank", type=int, default=0)
    p.add_argument("--leader-addr",
                   help="host:port of node 0 (jax.distributed coordinator)")
    p.add_argument("--dispatch-stream-port", type=int, default=5557,
                   help="leader port for the multihost dispatch stream "
                        "(engine/multihost.py; followers dial the "
                        "--leader-addr host at this port)")
    # routing / disagg
    p.add_argument("--router-mode", choices=["random", "round_robin"],
                   default="random")
    p.add_argument("--protocol", choices=["openai", "tokens"],
                   default="openai",
                   help="worker wire protocol for in=dyn://: openai = full "
                        "pipeline on the worker; tokens = core engine only "
                        "(preprocessing lives in a KV-routing processor)")
    p.add_argument("--remote-prefill", action="store_true",
                   help="decode worker: offload long prefills to the "
                        "prefill queue")
    p.add_argument("--is-prefill-worker", action="store_true",
                   help="serve the prefill side of disaggregation")
    p.add_argument("--role", choices=["serve", "prefill-publish"],
                   default="serve",
                   help="prefill-publish: prefill-as-a-service worker "
                        "(components/prefill_service.py) — pull the "
                        "prefill_publish queue + answer publish RPCs, "
                        "run prefill, publish prefix KV to the shared "
                        "object tier (--kv-remote-dir) for decode "
                        "fleets anywhere to admit via their measured "
                        "fetch-vs-recompute crossover")
    p.add_argument("--max-local-prefill-length", type=int, default=512)
    p.add_argument("--unconditional-disagg", action="store_true",
                   help="always prefill remotely (skip the threshold)")
    # batch mode
    p.add_argument("--trace-log-every", type=int, default=None,
                   help="log 1 of every N completed request traces "
                        "(slow/errored always log; skipped lines feed "
                        "nv_llm_trace_dropped_log_lines_total). Default: "
                        "env DYN_TRACE_LOG_EVERY or 1 (log all)")
    p.add_argument("--trace-log-slow-ms", type=float, default=None,
                   help="always log traces slower than this many ms, "
                        "regardless of sampling")
    p.add_argument("--output-path", help="batch: output JSONL path")
    p.add_argument("--max-tokens", type=int, default=256,
                   help="text/stdin/batch: generation budget")
    p.add_argument("--verbose", "-v", action="store_true")
    return p


def parse_io(io_args) -> Tuple[str, str]:
    src, out = "text", "echo_core"
    for a in io_args:
        if a.startswith("in="):
            src = a[3:]
        elif a.startswith("out="):
            out = a[4:]
        else:
            raise SystemExit(f"unrecognized positional arg {a!r} "
                             "(expected in=... / out=...)")
    return src, out


async def make_runtime(args):
    from ..runtime.distributed import DistributedRuntime
    if args.runtime_server:
        return await DistributedRuntime.connect(args.runtime_server,
                                                advertise=args.advertise_host)
    return DistributedRuntime.in_process()


def engine_config(args):
    from ..engine.config import EngineConfig
    return EngineConfig(
        max_model_len=args.max_model_len,
        kv_block_size=args.kv_block_size,
        num_kv_blocks=args.num_kv_blocks,
        max_num_seqs=args.max_num_seqs,
        enable_prefix_reuse=not args.no_prefix_reuse,
        host_kv_blocks=args.host_kv_blocks,
        kv_disk_dir=args.kv_disk_dir,
        kv_disk_blocks=args.kv_disk_blocks,
        kv_remote_dir=args.kv_remote_dir,
        kv_remote_blocks=args.kv_remote_blocks,
        kv_remote_admission=args.kv_remote_admission,
        prefill_chunk=args.prefill_chunk,
        **({"prefill_buckets": [int(b) for b in
                                args.prefill_buckets.split(",") if b]}
           if args.prefill_buckets else {}),
        decode_steps_per_dispatch=args.decode_steps_per_dispatch,
        decode_dispatch_pipeline=args.decode_dispatch_pipeline,
        lane_prefill_max_tokens=args.lane_prefill_max_tokens,
        ragged_dispatch=args.ragged,
        ragged_max_tokens=args.ragged_max_tokens,
        ragged_max_seq_rows=args.ragged_max_seq_rows,
        spec_k=args.spec_k,
        quantization=args.quantization,
        kv_quantization=args.kv_quantization,
        tp=args.tp, sp=args.sp, dp=args.dp, ep=args.ep, pp=args.pp)


def _model_name(args) -> str:
    if args.model_name:
        return args.model_name
    if args.model_path:
        return os.path.basename(os.path.normpath(args.model_path))
    return "echo"


async def build_engine(args, out: str, runtime):
    """→ (engine, mdc|None, core|None). Core engines get the preproc/backend
    link added by the caller; full engines speak OpenAI directly."""
    from ..llm.model_card import ModelDeploymentCard

    if out == "echo_full":
        from ..llm.engines.echo import EchoEngineFull
        return EchoEngineFull(), None, None
    if out == "echo_core":
        from ..llm.engines.echo import EchoEngineCore
        if not args.model_path:
            raise SystemExit("out=echo_core needs --model-path (tokenizer)")
        mdc = await asyncio.to_thread(
            ModelDeploymentCard.from_local_path,
            args.model_path, display_name=_model_name(args))
        return EchoEngineCore(), mdc, None
    if out.startswith("pystr:") or out.startswith("pytok:"):
        # user python-file engines (reference engines/python.rs:57-354)
        from ..llm.engines.python_file import (PythonFileEngineCore,
                                               PythonFileEngineFull)
        kind, _, path = out.partition(":")
        engine_args = {"model_path": args.model_path,
                       "model_name": _model_name(args)}
        if kind == "pystr":
            return PythonFileEngineFull(path, engine_args), None, None
        if not args.model_path:
            raise SystemExit("out=pytok needs --model-path (tokenizer)")
        mdc = await asyncio.to_thread(
            ModelDeploymentCard.from_local_path,
            args.model_path, display_name=_model_name(args))
        return PythonFileEngineCore(path, engine_args), mdc, None
    if out.startswith("dyn://") or out.count(".") == 2:
        from ..llm.engines.remote import RemoteEngine
        from ..runtime.distributed import Endpoint
        endpoint = Endpoint.parse_path(runtime, out)
        engine = await RemoteEngine.start(endpoint,
                                          router_mode=args.router_mode)
        return engine, None, None
    if out == "jax":
        from ..llm.engines.jax_engine import JaxEngine
        if not args.model_path:
            raise SystemExit("out=jax needs --model-path")
        mdc = await asyncio.to_thread(
            ModelDeploymentCard.from_local_path,
            args.model_path, display_name=_model_name(args))
        core = build_jax_core(args)
        engine = JaxEngine(core)
        if args.remote_prefill:
            from ..llm.disagg import DisaggEngine, DisaggregatedRouter
            router = DisaggregatedRouter(
                runtime, _model_name(args),
                max_local_prefill_length=args.max_local_prefill_length,
                conditional=not args.unconditional_disagg)
            await router.start()
            engine = DisaggEngine(core, runtime, router)
        return engine, mdc, core
    raise SystemExit(f"unknown out= engine {out!r}")


def build_jax_core(args):
    """The (possibly sharded) EngineCore from CLI flags. Every rank of a multi-
    host engine calls this with the same flags: bit-identical device state."""
    from ..engine.config import ModelConfig
    from ..engine.core import EngineCore
    from ..engine.flight_recorder import logged_build
    if not args.model_path:
        raise SystemExit("out=jax needs --model-path")
    try:
        ecfg = engine_config(args)   # validates pp/K/batch combos early
    except (ValueError, NotImplementedError) as e:
        raise SystemExit(str(e))
    import jax
    devices = jax.devices()   # a backend that cannot start raises here
    logger.info("jax backend: platform=%s device_kind=%s count=%d",
                devices[0].platform, devices[0].device_kind, len(devices))
    mesh = None
    if args.pp > 1:
        # pp(×tp) mesh: the stage ring crosses "pp" (the DCN-viable
        # axis — on a real multi-host deployment these are the ranks
        # that straddle hosts), in-stage collectives reduce over "tp"
        from ..parallel.pipeline_parallel import make_pp_mesh
        mesh = make_pp_mesh(args.pp, tp=args.tp)
    elif args.tp * args.sp * args.dp * args.ep > 1:
        from ..parallel.sharding import make_mesh
        mesh = make_mesh(dp=args.dp, tp=args.tp, sp=args.sp, ep=args.ep)
    model_cfg = ModelConfig.from_model_dir(args.model_path)
    params = None
    if not args.random_weights:
        from ..engine.weights import load_params_auto
        params = load_params_auto(args.model_path, model_cfg, mesh=mesh)
    return logged_build(EngineCore, model_cfg, ecfg, params=params, mesh=mesh)


async def run_follower_rank(args, out: str) -> None:
    """Follower rank of one multi-host engine: build the identical core,
    dial the leader's dispatch stream, live-replay until leader shutdown
    (engine/multihost.py; reference: sglang per-rank worker split,
    lib/llm/src/engines/sglang/worker.rs:304-336)."""
    if out != "jax":
        raise SystemExit("multi-host serving requires out=jax")
    from ..engine.multihost import connect_follower, run_follower
    core = build_jax_core(args)
    host = args.leader_addr.rsplit(":", 1)[0]
    sock = await asyncio.to_thread(
        connect_follower, f"{host}:{args.dispatch_stream_port}")
    logger.info("follower rank %d/%d replaying the leader dispatch stream",
                args.node_rank, args.num_nodes)
    stats = await asyncio.to_thread(run_follower, core, sock)
    logger.info("follower rank %d done: %s", args.node_rank, stats)


def link_pipeline(engine, mdc):
    """Core engines ride the canonical 6-stage link; full engines are the
    pipeline (input/common.rs:35-92)."""
    if mdc is None:
        return engine
    from ..llm.backend import Backend
    from ..llm.preprocessor import OpenAIPreprocessor
    from ..runtime import link
    return link(OpenAIPreprocessor(mdc), Backend(mdc), engine)


async def collect_chat_text(stream) -> str:
    """Fold a chat chunk stream to its first choice's text; raises on
    Annotated error items so failures surface instead of reading as empty
    output (delegates to the OpenAI aggregator — one fold implementation)."""
    from ..llm.protocols.openai import aggregate_chat_stream
    folded = await aggregate_chat_stream(stream)
    choices = folded.get("choices") or []
    if not choices:
        return ""
    return (choices[0].get("message") or {}).get("content") or ""


async def _serve_http(args, pipeline) -> None:
    from ..llm.http import HttpService
    svc = HttpService(port=args.http_port, host=args.http_host)
    name = _model_name(args)
    svc.manager.add_chat_model(name, pipeline)
    svc.manager.add_completion_model(name, pipeline)
    await svc.start()
    logger.info("serving %s on http://%s:%d/v1", name, args.http_host,
                args.http_port)
    await svc.run_forever()


async def run_http(args, pipeline, core) -> None:
    """The HTTP front end over ``pipeline``, until cancelled.

    With a local engine the front end, the pipeline and the engine loop
    run on a thread and an event loop of their own: an ``EngineCore``
    starts its loop on the first request, on whichever loop asks, so all
    that a request awaits lives there, and a call that blocks the
    caller's loop (a profiler writing its trace, a checkpoint; anything
    that releases the interpreter lock) stops no token stream. The
    caller's task only waits; cancelling it stops the engine on its own
    loop (``core.stop()``), then the thread. Where something under the
    pipeline already holds the caller's loop (a remote engine or a
    disaggregated router's connections, a dispatch stream's followers, an
    engine loop that is already running) the front end serves in place.
    """
    if (core is None or core.running or args.remote_prefill
            or args.num_nodes > 1):
        await _serve_http(args, pipeline)
        return
    import threading
    caller = asyncio.get_running_loop()
    outcome = caller.create_future()
    started = threading.Event()
    on_thread = {}

    async def serve() -> None:
        on_thread["loop"] = asyncio.get_running_loop()
        on_thread["task"] = asyncio.current_task()
        started.set()
        try:
            await _serve_http(args, pipeline)
        finally:
            await core.stop()      # the engine loop is a task of THIS loop

    def tell(error) -> None:
        if outcome.done():
            return
        if error is None:
            outcome.set_result(None)
        else:
            outcome.set_exception(error)

    def thread_main() -> None:
        error = None
        try:
            asyncio.run(serve())
        except asyncio.CancelledError:
            pass
        except BaseException as e:  # noqa: BLE001 — the caller's to raise
            error = e
        started.set()
        try:
            caller.call_soon_threadsafe(tell, error)
        except RuntimeError:       # the caller's loop is gone already
            pass

    thread = threading.Thread(target=thread_main, name="http-serve",
                              daemon=True)
    thread.start()
    try:
        await outcome
    except asyncio.CancelledError:
        await asyncio.to_thread(started.wait)
        try:
            on_thread["loop"].call_soon_threadsafe(on_thread["task"].cancel)
        except (KeyError, RuntimeError):   # never started, or over already
            pass
        await asyncio.to_thread(thread.join)
        raise


async def run_text(args, pipeline, interactive: bool) -> None:
    from ..runtime import Context
    name = _model_name(args)
    loop = asyncio.get_running_loop()
    if interactive and sys.stdin.isatty():
        print(f"model: {name} — empty line or Ctrl-D to exit")
    while True:
        if interactive and sys.stdin.isatty():
            print("> ", end="", flush=True)
        line = await loop.run_in_executor(None, sys.stdin.readline)
        if not line:
            return                      # EOF
        if not line.strip():
            if interactive:
                return                  # empty line exits the REPL
            continue                    # piped input: skip blanks, keep going
        req = {"model": name, "max_tokens": args.max_tokens, "stream": True,
               "messages": [{"role": "user", "content": line.strip()}]}
        stream = await pipeline.generate(Context(req))
        print(await collect_chat_text(stream))


async def run_batch(args, pipeline, path: str) -> None:
    """batch:FILE.jsonl — one JSON per line: {"text": ...} (completion
    prompt) or {"messages": [...]} (chat). Results go to --output-path
    (default: <input>.out.jsonl)."""
    from ..runtime import Context
    name = _model_name(args)
    out_path = args.output_path or (path.rsplit(".jsonl", 1)[0] + ".out.jsonl")
    done = 0
    failed = 0

    def _read_lines() -> list:
        with open(path) as fin:
            return fin.readlines()

    # file reads/writes ride to_thread so generation on this loop (e.g. a
    # co-located in-process engine) keeps stepping during the I/O
    lines = await asyncio.to_thread(_read_lines)
    fout = await asyncio.to_thread(open, out_path, "w")
    try:
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                d = json.loads(line)
                messages = d.get("messages") or [
                    {"role": "user",
                     "content": d.get("text", d.get("prompt", ""))}]
                req = {"model": name, "stream": True,
                       "max_tokens": d.get("max_tokens", args.max_tokens),
                       "messages": messages}
                if "temperature" in d:
                    req["temperature"] = d["temperature"]
                stream = await pipeline.generate(Context(req))
                text = await collect_chat_text(stream)
                out_line = json.dumps({**d, "response": text}) + "\n"
            except json.JSONDecodeError as e:
                failed += 1
                out_line = json.dumps({"input": line,
                                       "error": str(e)}) + "\n"
            except Exception as e:  # noqa: BLE001 — per-row isolation
                failed += 1
                out_line = json.dumps({**d, "error": str(e)}) + "\n"
            await asyncio.to_thread(fout.write, out_line)
            done += 1
    finally:
        await asyncio.to_thread(fout.close)
    level = logging.WARNING if failed else logging.INFO
    logger.log(level, "batch complete: %d requests (%d failed) → %s",
               done, failed, out_path)
    if failed:
        raise SystemExit(1)


async def run_worker_endpoint(args, engine, pipeline, core, runtime,
                              path: str, mdc=None) -> None:
    """in=dyn://ns/comp/ep — serve as a discoverable worker instance
    (input/endpoint.rs:34-115): stats handler publishes ForwardPassMetrics;
    KV events go to the component's kv_events subject for KV-aware routers.

    protocol=openai serves the full pipeline (preproc+detok on the worker,
    the dynamo-run shape); protocol=tokens serves the bare core engine (a
    KV-routing processor tokenizes and detokenizes, the examples/llm
    Processor→Router→Worker shape)."""
    from ..llm.protocols.annotated import encode_annotated_json
    from ..llm.protocols.common import PreprocessedRequest
    from ..runtime.distributed import Endpoint
    endpoint = Endpoint.parse_path(runtime, path)
    stats_handler = None
    if core is not None:
        def stats_handler():
            from ..runtime import netstore
            d = core.metrics().to_dict()
            # process-wide daemon-link counters ride the worker's scrape
            # (nv_llm_netstore_retries_total / _deadline_exceeded_total)
            d["netstore_retries_total"] = netstore.retries_total()
            d["netstore_deadline_exceeded_total"] = \
                netstore.deadline_exceeded_total()
            return d
        await _wire_kv_events(core, runtime, endpoint)
        await _wire_spec_config(core, runtime, endpoint.namespace)
        _wire_kv_admin(core, runtime, endpoint.namespace)
        _wire_kv_weights(runtime, endpoint.namespace)
        _wire_faults(runtime, endpoint.namespace)
        _wire_tracing(args, core, runtime, endpoint)
        if getattr(args, "tenancy", False):
            # multi-tenant quotas (llm/tenancy.py): per-tenant block
            # ledger across the KV tiers + live policy watch
            # (llmctl tenant {set-weight,set-quota})
            core.enable_tenancy()
            _wire_tenants(runtime, endpoint.namespace)
        if args.kv_fabric:
            # fleet KV fabric (llm/kv/fabric.py): serve our disk/host
            # blocks at dyn://{ns}/{comp}/kv_fabric, fetch peers' —
            # the G4 rung behind the same KvBlockManager cascade
            from ..llm.kv.fabric import KvFabric
            await KvFabric.attach(core, runtime, endpoint)
    if args.protocol == "tokens":
        if mdc is None:
            raise SystemExit(
                "--protocol tokens needs a token-level engine "
                "(out=jax or out=echo_core), not a full-pipeline one")
        await endpoint.serve(
            engine,
            decode_req=lambda raw: PreprocessedRequest.from_dict(
                json.loads(raw)),
            encode_resp=encode_annotated_json,
            stats_handler=stats_handler)
    else:
        await endpoint.serve(pipeline, encode_resp=encode_annotated_json,
                             stats_handler=stats_handler)
        # register the model entries under our lease so discovery-driven
        # frontends pick the model up — and drop it when this worker dies
        if args.model_path or args.model_name:
            from ..llm.discovery import ModelEntry, register_model
            lease = await runtime.primary_lease()
            for mt in ("chat", "completion"):
                await register_model(runtime, ModelEntry(
                    name=_model_name(args), endpoint=endpoint.path,
                    model_type=mt), lease_id=lease.id)
            # registry card (llm/registry.py): the model's deployment
            # record — tokenizer ref, geometry, program-set key — under
            # the same lease, so multi-model frontends can multiplex
            # the OpenAI `model` field onto this fleet
            from ..llm.registry import RegistryCard, register_card
            geometry = {
                "tp": args.tp, "pp": args.pp, "sp": args.sp,
                "quantization": args.quantization or None,
                "kv_quantization": args.kv_quantization or None,
                "spec_k": args.spec_k, "ragged": bool(args.ragged),
                "max_seq_len": args.max_model_len,
            }
            await register_card(runtime, RegistryCard(
                name=_model_name(args), endpoint=endpoint.path,
                model_path=args.model_path,
                kv_block_size=(core.cfg.kv_block_size if core is not None
                               else args.kv_block_size or 16),
                geometry=geometry), lease_id=lease.id)
    logger.info("worker serving %s (%s protocol)", endpoint.path,
                args.protocol)
    await asyncio.Event().wait()


def _wire_tenants(runtime, namespace: str) -> None:
    """llmctl tenant plumbing (llm/tenancy.py): converge to the stored
    tenant/control/{ns} policy table and keep applying live updates —
    the TIER_WEIGHTS retune pattern for tenant weights/quotas."""
    from ..llm.tenancy import watch_tenants_loop
    asyncio.get_running_loop().create_task(
        watch_tenants_loop(runtime, namespace), name="tenant-watch")


def _wire_tracing(args, core, runtime, endpoint) -> None:
    """Fleet tracing wiring (docs/observability.md): configure the
    process tracer's log sampling, publish every finished trace over the
    component's trace_events subject (the collector on the metrics
    service assembles the fleet trees), and watch the trace/control key
    so ``llmctl trace dump`` can pull this worker's flight recorder."""
    from ..components.trace_collector import wire_trace_publisher
    from ..engine.flight_recorder import watch_trace_dump_loop
    from ..runtime.tracing import tracer

    tracer.configure(log_every=getattr(args, "trace_log_every", None),
                     slow_ms=getattr(args, "trace_log_slow_ms", None))
    component = runtime.namespace(endpoint.namespace).component(
        endpoint.component)
    wire_trace_publisher(component)
    asyncio.get_running_loop().create_task(
        watch_trace_dump_loop(core, runtime, endpoint.namespace),
        name="trace-dump-watch")


async def _wire_kv_events(core, runtime, endpoint) -> None:
    """Attach a KvEventPublisher to the engine's block pool → bus subject
    ``evt.{ns}.{comp}.kv_events`` (reference kv_router/publisher.rs)."""
    from ..llm.kv_router.publisher import KvEventPublisher
    component = runtime.namespace(endpoint.namespace).component(
        endpoint.component)
    lease = await runtime.primary_lease()

    async def sink(ev) -> None:
        await component.publish_event("kv_events", ev)

    pub = KvEventPublisher(worker_id=lease.id, sink=sink)
    core.kv_event_publisher = pub
    # route pool events through the core's tier-aware wrappers: a device
    # eviction whose hash survives in the host/disk tier DEMOTES the
    # announce (tier-tagged re-store) instead of removing it, and disk
    # spills/evictions announce with tier="disk"
    core.kv_manager.pool.on_stored = core._on_block_stored
    core.kv_manager.pool.on_removed = core._on_block_removed

    if core.disk_store is not None and len(core.disk_store) > 0:
        # warm-started disk tier: announce the recovered prefixes so the
        # router's radix index routes matching prompts here for a
        # promote instead of a cold recompute elsewhere (the same
        # reannounce() hook the lease-reclaim recovery uses)
        # off-loop: the remote-tier inventory walk reads every durable
        # object's chain meta (per-object file I/O, proportional to the
        # warm tier) — the engine loop isn't serving yet, but frontends
        # sharing this process's loop are (DL001, found by the typed-
        # chain resolution this PR added)
        n = await asyncio.to_thread(core.reannounce_kv)
        logger.info("announced %d KV blocks at bring-up (%d disk-"
                    "resident from the previous run)", n,
                    len(core.disk_store))

    # transient lease expiry → reclaim replays discovery keys but the
    # router's radix index of OUR blocks was wiped by the DELETE events;
    # re-announce the pool so KV-aware routing recovers instead of
    # silently degrading to load-balancing (KNOWN_ISSUES, fixed this PR)
    prev = getattr(runtime.store, "on_lease_reclaimed", None)

    def reclaimed(lease_id: int) -> None:
        if prev is not None:
            prev(lease_id)
        if lease_id == lease.id:
            n = core.reannounce_kv()
            logger.info("re-announced %d stored KV blocks after lease "
                        "reclaim", n)

    runtime.store.on_lease_reclaimed = reclaimed


async def _wire_spec_config(core, runtime, namespace: str) -> None:
    """Live speculative-decoding retune (llmctl spec set-k/off): load the
    stored draft budget for this namespace, then watch the config key and
    move ``core.spec_k_live`` within [0, cfg.spec_k] — the compiled
    verify program never widens at runtime (engine/spec/admin.py)."""
    from ..engine.spec import SpecConfig, spec_config_key

    key = spec_config_key(namespace)

    def apply(raw: bytes) -> None:
        try:
            k = SpecConfig.from_json(raw).k
        except (ValueError, KeyError):
            logger.warning("ignoring malformed spec config at %s", key)
            return
        core.spec_k_live = max(0, min(k, core.cfg.spec_k))
        if k > core.cfg.spec_k:
            logger.warning(
                "spec set-k %d exceeds the compiled maximum %d — "
                "clamped (restart with a larger --spec-k to widen the "
                "verify program)", k, core.cfg.spec_k)
        logger.info("speculation live draft budget -> %d",
                    core.spec_k_live)

    from ..runtime.kvstore import WatchEventType
    entry = await runtime.store.kv_get(key)
    if entry is not None:
        apply(entry.value)
    watcher = await runtime.store.watch_prefix(key)

    async def watch_loop() -> None:
        async for ev in watcher:
            if ev.type == WatchEventType.PUT:
                apply(ev.entry.value)

    asyncio.get_running_loop().create_task(watch_loop(),
                                           name="spec-config-watch")


def _wire_kv_admin(core, runtime, namespace: str) -> None:
    """llmctl kv {status,flush} plumbing (llm/kv/admin.py): publish this
    worker's tier snapshot and act on flush/clear commands. Wired only
    when any offload tier exists — a pure-HBM engine has nothing to
    report or flush."""
    if core.kv_manager.host_pool is None and core.disk_store is None:
        return
    from ..llm.kv.admin import publish_status_loop, watch_control_loop
    loop = asyncio.get_running_loop()
    loop.create_task(publish_status_loop(core, runtime, namespace),
                     name="kv-admin-status")
    loop.create_task(watch_control_loop(core, runtime, namespace),
                     name="kv-admin-control")


def _wire_kv_weights(runtime, namespace: str) -> None:
    """llmctl kv set-weights plumbing: apply the namespace's stored tier
    weights and keep applying live updates (llm/kv/admin.py
    watch_weights_loop). Runs on every worker — and any process hosting
    a KV router gets the same watch via KvRoutedEngine — so the fleet's
    scoring stays coherent."""
    from ..llm.kv.admin import watch_weights_loop
    asyncio.get_running_loop().create_task(
        watch_weights_loop(runtime, namespace), name="kv-weights-watch")


def _wire_faults(runtime, namespace: str) -> None:
    """llmctl faults plumbing (runtime/faults.py): apply the
    namespace's stored failpoint table and keep applying live updates —
    the fleet-wide chaos-drill lever (docs/chaos.md)."""
    from ..runtime.faults import watch_faults_loop
    asyncio.get_running_loop().create_task(
        watch_faults_loop(runtime, namespace), name="faults-watch")


async def run_prefill_worker(args, core, runtime) -> None:
    from ..llm.disagg import PrefillWorker
    worker = await PrefillWorker(core, runtime).start()
    logger.info("prefill worker pulling queue (engine ready)")
    try:
        await asyncio.Event().wait()
    finally:
        await worker.stop()


async def run_prefill_publish(args, core, runtime, src: str) -> None:
    """--role prefill-publish: the prefill-as-a-service worker
    (components/prefill_service.py). Serves publish/status RPCs at a
    discoverable endpoint (in=dyn://… or the default
    dyn://{ns}/prefill/prefill_publish) and pulls the shared
    prefill_publish work queue; published prefix KV lands in the
    --kv-remote-dir object tier for any decode fleet to admit."""
    from ..components.prefill_service import (PREFILL_PUBLISH_ENDPOINT,
                                              PrefillService)
    from ..runtime.distributed import Endpoint
    try:
        svc = await PrefillService(core, runtime).start()
    except ValueError as e:
        raise SystemExit(str(e))
    if src.startswith("dyn://") or src.count(".") == 2:
        endpoint = Endpoint.parse_path(runtime, src)
    else:
        endpoint = Endpoint(runtime, args.namespace, "prefill",
                            PREFILL_PUBLISH_ENDPOINT)

    def stats_handler():
        d = core.metrics().to_dict()
        d.update(svc.stats())
        return d

    await endpoint.serve(svc, decode_req=lambda raw: json.loads(raw),
                         stats_handler=stats_handler)
    logger.info("prefill-publish worker serving %s (object root %s)",
                endpoint.path, core.cfg.kv_remote_dir)
    try:
        await asyncio.Event().wait()
    finally:
        await svc.stop()


async def amain(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from ..runtime.log import setup_logging
    setup_logging('debug' if args.verbose else None)
    src, out = parse_io(args.io)

    if args.model_path and not os.path.isdir(args.model_path):
        # hub resolution (reference launch/dynamo-run/src/hub.rs: a model
        # NAME is fetched into the local cache; a directory passes through)
        from ..llm.hub import HubError, fetch_model
        try:
            # hub download + manifest validation is bulk file I/O — keep
            # it off the loop even at startup (a co-located server on
            # this loop would stall behind a 70B snapshot check)
            args.model_path = await asyncio.to_thread(
                fetch_model, args.model_path)
        except HubError as e:
            raise SystemExit(str(e))

    # Multi-host join must precede any JAX use in this process. Every host
    # runs the same command with its own --node-rank; rank 0 is the leader
    # (scheduler + frontend + token egress) and streams its dispatch
    # sequence to the followers, which live-replay it so every rank enters
    # the SPMD collectives in lockstep (engine/multihost.py; reference:
    # lib/llm/src/engines/vllm/ray.rs leader/follower).
    from ..parallel.multihost import MultiNodeConfig, initialize_multihost
    if args.num_nodes > 1:
        # validate BEFORE weights load / listener bind, on every rank — the
        # same constraints DispatchStreamLeader.attach enforces, surfaced
        # as CLI config errors
        if out != "jax":
            raise SystemExit("multi-host serving requires out=jax")
        if args.decode_steps_per_dispatch <= 1:
            raise SystemExit(
                "multi-host serving requires --decode-steps-per-dispatch "
                "> 1 (no follower has replayed the one-step path's per-slot "
                "chained dispatches)")
    initialize_multihost(MultiNodeConfig(
        num_nodes=args.num_nodes, node_rank=args.node_rank,
        leader_addr=args.leader_addr))

    if args.num_nodes > 1 and args.node_rank > 0:
        await run_follower_rank(args, out)
        return

    runtime = await make_runtime(args)
    stream = None
    try:
        engine, mdc, core = await build_engine(args, out, runtime)
        if args.num_nodes > 1:
            if core is None:
                raise SystemExit("multi-host serving requires out=jax")
            from ..engine.multihost import DispatchStreamLeader
            stream = DispatchStreamLeader(
                port=args.dispatch_stream_port,
                num_followers=args.num_nodes - 1)
            stream.attach(core)
            logger.info("waiting for %d follower rank(s) on dispatch "
                        "stream port %d", args.num_nodes - 1, stream.port)
            stream.wait_for_followers()
        if args.is_prefill_worker:
            if core is None:
                raise SystemExit("--is-prefill-worker requires out=jax")
            await run_prefill_worker(args, core, runtime)
            return
        if args.role == "prefill-publish":
            if core is None:
                raise SystemExit("--role prefill-publish requires out=jax")
            await run_prefill_publish(args, core, runtime, src)
            return
        pipeline = link_pipeline(engine, mdc)
        if src == "http":
            await run_http(args, pipeline, core)
        elif src == "text":
            await run_text(args, pipeline, interactive=True)
        elif src == "stdin":
            await run_text(args, pipeline, interactive=False)
        elif src.startswith("batch:"):
            await run_batch(args, pipeline, src[len("batch:"):])
        elif src.startswith("dyn://") or src.count(".") == 2:
            await run_worker_endpoint(args, engine, pipeline, core, runtime,
                                      src, mdc=mdc)
        elif src == "none":
            await asyncio.Event().wait()
        else:
            raise SystemExit(f"unknown in= source {src!r}")
    finally:
        if 'core' in locals() and core is not None:
            try:
                await core.stop()
            except asyncio.CancelledError:
                # SIGINT: asyncio.run cancelled amain and the cancel
                # landed at stop()'s first await — finish the graceful
                # stop anyway (it flushes the host KV tier to the disk
                # store; losing it would turn every Ctrl-C restart into
                # a partially-cold start), then let the cancel proceed
                await core.stop()
                raise
        if stream is not None:
            stream.close()   # followers get __shutdown__, exit cleanly
        await runtime.shutdown()


def main() -> None:
    if "out=jax" in sys.argv[1:]:
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
