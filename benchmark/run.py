#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process each time. It asserts a TPU first (no CPU branch: the CPU
rehearsal is ``benchmark/selftest.py``), builds the launcher's own engine and
HTTP service at the configuration's published widths with weights made on
the device from ``--seed``, warms the programs this cell's traffic uses,
holds the served path to the float32 reference, and then measures for
``--seconds`` under load offered by a process of its own
(``benchmark/loadgen.py``). Everything before the window opens is ``setup_s``.

Driven by data: the cell, its configuration and its traffic mix are found
by name in ``BENCHMARK.json``, ``benchmark/configs/<config>.json`` and
``benchmark/traffic/<mix>.json``; each per-layer metric is read by
``benchmark/layer_metrics/<metric>.py``; the plain reference a
configuration is held to is the module its file names
(``"reference": "<name>"`` → ``benchmark/references/<name>.py``; none named:
``benchmark/reference.py``), and the functions that price a kernel at its
shapes are that family's costs module (``references/<name>_costs.py``; none
named: ``benchmark/costs.py``), which a kernel's reader finds in its ``ctx``.
Adding any of them edits no file that is there.

The last line of standard output is the result object; earlier lines are
``# key: value`` notes. A builder-only mode, never the driver's:

    python3 benchmark/run.py --sweep <mix> --config <config> [--rates a,b,c]

builds the engine once and runs windows at rising rates to find the knee.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import stats  # noqa: E402
import traffic  # noqa: E402
from server import (MODEL_NAME, CacheWatch, Server, note,  # noqa: E402
                    require_tpu, write_model_dir)

# keys of a configuration file that are the benchmark's own, not the
# published config.json's
CONFIG_EXTRAS = ("source", "reduced", "assumed", "deployment",
                 "memory_analysis", "notes", "reference")
PROBE_OUTPUT_TOKENS = 8
PROBE_PROMPTS = 2
# A traced window ends with the profiler: it runs for TRACE_SECONDS and stops
# TRACE_TAIL_S before the window closes. The profiler slows the host (about
# 40,000 device events a second to record), which at 0.8 of the knee is
# enough to build a backlog; so the metrics read from spans and flight
# records come from the part of the window before it starts.
TRACE_SECONDS = 4.0
TRACE_TAIL_S = 1.0
WORK_DIR = os.path.join(ROOT, ".bench_work")      # git-ignored scratch


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_config(bench: dict, name: str) -> dict:
    entry = next(c for c in bench["configs"] if c["name"] == name)
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def hf_config(config: dict) -> dict:
    return {k: v for k, v in config.items() if k not in CONFIG_EXTRAS}


# where the readers of each group of metrics in BENCHMARK.json live
READER_DIRS = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}


def load_by_path(module_name: str, path: str):
    """A module of the benchmark that is found by a name in its data."""
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def reference_module(config: dict):
    """The plain reference this configuration is held to: the forward
    pass (``logits_for``) and the breakages it must catch. A configuration
    file names it (``"reference": "deepseek_v2"`` →
    ``references/deepseek_v2.py``); one that names none is of the llama
    family and gets ``reference.py``. The comparison and its tolerance are
    ``reference.compare``'s for every family."""
    name = config.get("reference")
    if name is None:
        import reference
        return reference
    path = os.path.join(HERE, "references", f"{name}.py")
    if not os.path.exists(path):
        raise SystemExit(f"the configuration names the reference {name!r}; "
                         f"there is no {os.path.relpath(path, ROOT)}")
    return load_by_path("reference_" + name.replace("-", "_"), path)


def costs_module(config: dict):
    """The operations and bytes of this configuration's family, and the
    traced ops that are each kernel's: what a kernel's reader prices with
    (``ctx["costs"]``), so that one reader serves the kernel at every
    configuration's shapes. Found by the name the file gives its reference
    (``"reference": "deepseek_v32"`` → ``references/deepseek_v32_costs.py``);
    a file that names none is of the llama family and gets ``costs.py``.
    None where the family brought no costs module."""
    name = config.get("reference")
    module = "costs" if name is None else f"references.{name}_costs"
    if importlib.util.find_spec(module) is None:
        return None
    return importlib.import_module(module)


def metric_reader(group: str, name: str):
    """The reader of one metric, found by the metric's name: a file of its
    own under ``end_to_end/`` or ``layer_metrics/``. Where one quantity
    moves different end-to-end metrics in different cells it is listed
    once per group of cells with a suffix (``step.decode_cycle_ms.open``,
    ``.closed``) and read by the file of the name without it."""
    path = os.path.join(HERE, READER_DIRS[group], f"{name}.py")
    if not os.path.exists(path):
        path = os.path.join(HERE, READER_DIRS[group],
                            f"{name.rpartition('.')[0]}.py")
    return load_by_path(
        group + "_" + name.replace(".", "_").replace("-", "_"), path).read


def cell_metrics(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` that ``cell`` reports (``cell`` None: those
    every cell reports)."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------ set-up

def prepare(bench: dict, config_name: str, mix_name: str) -> tuple:
    """→ (launcher flags, published config, mix, model dir, reference
    module): the model directory is written, nothing has touched the chip
    yet."""
    config = load_config(bench, config_name)
    hf = hf_config(config)
    model_dir = os.path.join(WORK_DIR, "model", config_name)
    write_model_dir(model_dir, hf)
    return (list(config["deployment"]["flags"]), hf,
            traffic.load_mix(mix_name), model_dir, reference_module(config))


def enable_cache() -> CacheWatch:
    """The persistent compile cache where JAX_COMPILATION_CACHE_DIR says,
    else at the program's fixed path inside the checkout; every program is
    kept, however quick its compilation (the sub-second ones are most of
    what a warm start would otherwise compile again)."""
    import jax
    from dynamo_tpu.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return CacheWatch(cache_dir)


class LoadGen:
    """The load generator's process, and the end of it."""

    def __init__(self):
        self.proc = None

    async def start(self, cfg: dict) -> dict:
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, os.path.join(HERE, "loadgen.py"),
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            limit=1 << 28)
        self.proc.stdin.write((json.dumps(cfg) + "\n").encode())
        await self.proc.stdin.drain()
        return await self._read()

    async def _read(self) -> dict:
        line = await self.proc.stdout.readline()
        if not line:
            raise RuntimeError("the load generator ended without a word "
                               f"(exit code {await self.proc.wait()})")
        return json.loads(line)

    async def go(self, t0: float) -> None:
        self.proc.stdin.write((json.dumps({"go": t0}) + "\n").encode())
        await self.proc.stdin.drain()

    async def result(self) -> dict:
        out = await self._read()
        await self.proc.wait()
        return out["result"]

    async def stop(self) -> None:
        if self.proc is not None and self.proc.returncode is None:
            self.proc.kill()
            await self.proc.wait()


async def warm_up(srv: Server, mix: dict, vocab: int, seed: int) -> list:
    """Each prefill bucket this mix can use, and the decode program, once,
    over HTTP."""
    import numpy as np
    rng = np.random.default_rng(seed ^ 0x5eed)
    buckets = traffic.buckets_used(mix, srv.core.cfg.prefill_buckets)
    for b in buckets:
        t0 = time.monotonic()
        await srv.complete(rng.integers(0, vocab, size=b).tolist(), 2)
        note(f"warm_up[prefill-{b}+decode]",
             f"{time.monotonic() - t0:.2f} s")
    warm_defrag(srv.core)
    return buckets


def warm_defrag(core) -> None:
    """The engine compacts a sequence's blocks when it has a quiet moment
    (``_maybe_defrag``): one copy program per power-of-two block count,
    built on first use, which under load is inside the window. Build them
    now, on the engine's own pool, as copies of the trash block onto
    itself (block 0: its content is never read). Called between requests,
    on the engine loop's own thread, with nothing dispatched. If the
    program no longer has this entry point the count of programs built
    inside the window is still the judge."""
    import jax
    cfg = core.cfg
    if not (cfg.kv_contig_alloc and cfg.kv_defrag_threshold > 0):
        return
    t0 = time.monotonic()
    try:
        from dynamo_tpu.engine.block_copy import move_blocks
        n = 2
        while n <= cfg.kv_defrag_max_blocks:
            core.kv = move_blocks(core.kv, [0] * n, [0] * n,
                                  cfg.kv_block_size)
            n *= 2
        jax.block_until_ready(core.kv)
    except (ImportError, AttributeError, TypeError) as e:
        note("warm_up[defrag copies]", f"not warmed: {e!r}")
        return
    note("warm_up[defrag copies]", f"{time.monotonic() - t0:.2f} s")


async def probe(srv: Server, hf: dict, mix: dict, seed: int, ref) -> tuple:
    """Seeded prompts through the served path, held to the configuration's
    reference ``ref``. → (report, prompts, served ids per prompt). The
    prompt is as long as the mix's shortest (48 at least), so the programs
    probed are the cell's."""
    import numpy as np
    import reference
    rng = np.random.default_rng(seed ^ 0x9e0be)
    n_prompt = max(48, traffic.size_bound(mix["prompt_tokens"], "min"))
    vocab = int(hf["vocab_size"])
    served, worst = [], {"ok": True, "worst_logprob_err_std": 0.0,
                         "worst_argmax_gap_std": 0.0}
    prompts = [rng.integers(0, vocab, size=n_prompt).tolist()
               for _ in range(PROBE_PROMPTS)]
    for prompt in prompts:
        got = await srv.complete(prompt, PROBE_OUTPUT_TOKENS)
        rep = reference.compare(srv.core.params, hf, prompt, got["ids"],
                                got["logprobs"], forward=ref.logits_for)
        served.append(got["ids"])
        worst["ok"] = worst["ok"] and rep["ok"]
        for k in ("worst_logprob_err_std", "worst_argmax_gap_std"):
            worst[k] = max(worst[k], rep[k])
        worst["logits_std"] = rep["logits_std"]
        worst["tol_std"] = rep["tol_std"]
    worst["prompt_tokens"] = n_prompt
    return worst, prompts, served


# ------------------------------------------------------------- the window

class FlightDrain:
    """The engine's flight records of the window, taken from its ring
    (512 records) often enough that none is lost, by ``records_total``."""

    def __init__(self, flight):
        self.flight = flight
        self.seen = flight.records_total
        self.records = []
        self.lost = 0

    def drain(self) -> None:
        total = self.flight.records_total
        new = total - self.seen
        if new <= 0:
            return
        ring = self.flight.dump()
        if new > len(ring):
            self.lost += new - len(ring)
            new = len(ring)
        self.records.extend(ring[-new:])
        self.seen = total

    async def until(self, epoch: float) -> None:
        """Sleeps to ``epoch``, draining four times a second."""
        while time.time() < epoch:
            await asyncio.sleep(min(0.25, max(0.0, epoch - time.time())))
            self.drain()


async def measure(srv: Server, gen: LoadGen, mix: dict, seconds: float,
                  trace: bool, cache: CacheWatch) -> dict:
    """Opens the window, waits for it, returns what was seen."""
    import jax
    core = srv.core
    spans = []
    drain = FlightDrain(core.flight)
    from dynamo_tpu.runtime.tracing import tracer
    if trace:
        tracer.on_finish.append(spans.append)
    ramp = float(mix.get("ramp_s", 0))
    t0 = time.time() + 0.5
    await gen.go(t0)
    open_at, close_at = t0 + ramp, t0 + ramp + seconds
    await asyncio.sleep(max(0.0, open_at - time.time()))
    programs_at_open = cache.programs
    drain.drain()
    drain.records.clear()
    setup_s = open_at - T_PROCESS_START
    trace_dir, traced = os.path.join(WORK_DIR, "trace"), None
    quiet_until = close_at          # the profiler is off before this
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        length = min(TRACE_SECONDS, max(1.0, seconds / 2))
        quiet_until = close_at - TRACE_TAIL_S - length
        await drain.until(quiet_until)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        anchor_ns = time.time_ns()
        with jax.profiler.TraceAnnotation("bench_anchor"):
            pass
        await drain.until(time.time() + length)
        jax.profiler.stop_trace()
        traced = {"dir": trace_dir, "anchor_epoch_ns": anchor_ns}
    await drain.until(close_at)
    programs_in_window = cache.programs - programs_at_open
    window_records = [r for r in drain.records
                      if open_at <= r["t"] < close_at]
    result = await gen.result()
    if trace:
        tracer.on_finish.remove(spans.append)
    quiet_spans = [t for t in spans if open_at <= t["start_epoch"]
                   and t["start_epoch"] + t["total_ms"] / 1e3 < quiet_until]
    return {"load": result, "setup_s": setup_s, "flight": window_records,
            "quiet_flight": [r for r in window_records
                             if r["t"] < quiet_until],
            "quiet_s": quiet_until - open_at, "quiet_spans": quiet_spans,
            "flight_lost": drain.lost, "traced": traced,
            "programs_in_window": programs_in_window}


def read_metrics(bench: dict, cell: str, group: str, ctx: dict) -> dict:
    """Every metric of ``group`` that this cell reports, by its reader; a
    reader that finds nothing to read returns None and is left out."""
    out = {}
    for m in cell_metrics(bench, cell, group):
        value = metric_reader(group, m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def note_samples(load: dict) -> None:
    n = len(load["ttft_ms"])
    note("samples", f"ttft={n} itl={len(load['itl_ms'])} "
         f"ttft_tail_percentile={stats.supported_tail(n):.1f} "
         f"(p95 asked; the highest with ten samples beyond it)")


def reduce_trace(traced: dict, flight: list) -> dict:
    """The device's busy time over the profiler's window, and its idle
    gaps labelled by the flight record whose host interval covers them."""
    import trace_reduce
    path = trace_reduce.find_xplane(traced["dir"])
    scopes = {}
    events = trace_reduce.device_events(path, scopes=scopes)
    host_spans = []
    anchors = trace_reduce.host_events(path, "bench_anchor")
    if anchors:
        # profiler clock − epoch clock, at the anchor
        shift = anchors[0][0] - traced["anchor_epoch_ns"]
        for r in flight:
            dur_ms = (r.get("host_ms") if r["kind"] == "prefill"
                      else r.get("device_ms", 0.0) + r.get("host_gap_ms", 0.0))
            if dur_ms is None:
                continue
            end = int(r["t"] * 1e9) + shift
            host_spans.append((r["kind"], end - int(dur_ms * 1e6), end))
    note("trace", f"file={os.path.basename(path)} devices={len(events)} "
         f"events={sum(len(e) for e in events.values())} "
         f"anchor={'found' if anchors else 'missing'}")
    out = trace_reduce.reduce(events, host_spans=host_spans, top=7,
                              scopes=scopes)
    out["programs"] = trace_reduce.program_table(trace_reduce.device_events(
        path, lines=(trace_reduce.MODULE_LINE,)))
    # printed: the programs first (prefill against decode), then the ops
    out["device_ops"] = [[f"program {name}", s]
                         for name, s, _ in out["programs"][:3]
                         ] + out["device_ops"]
    return out


# ------------------------------------------------------------- a whole run

async def run_cell(bench: dict, cell: dict, seed: int, seconds: float,
                   trace: bool, devices, cache: CacheWatch,
                   keep_trace: bool = False) -> dict:
    flags, hf, mix, model_dir, ref = prepare(bench, cell["config"],
                                             cell["traffic"])
    vocab = int(hf["vocab_size"])
    note("reference", os.path.splitext(os.path.basename(ref.__file__))[0])
    note("setup[model dir written]", f"{time.time() - T_PROCESS_START:.1f} s")
    gen = LoadGen()
    try:
        async with Server(model_dir, flags, seed & 0x7fffffff) as srv:
            note("setup[engine built]",
                 f"{time.time() - T_PROCESS_START:.1f} s "
                 f"(build {srv.build_s:.1f} s)")
            ready = await gen.start({
                "base": srv.base, "model": MODEL_NAME, "mix": mix,
                "seed": seed, "seconds": seconds, "vocab": vocab})
            note("offered", f"requests={ready['requests']} loop={mix['loop']}")
            buckets = await warm_up(srv, mix, vocab, seed)
            verdict, prompts, served = await probe(srv, hf, mix, seed, ref)
            note("reference_before_window", json.dumps(verdict))
            note("setup[probed]", f"{time.time() - T_PROCESS_START:.1f} s")
            cache.report("set-up")
            seen = await measure(srv, gen, mix, seconds, trace, cache)
            load = seen["load"]
            after, _, again = await probe(srv, hf, mix, seed, ref)
            note("reference_after_window", json.dumps(after))
            note("probe_repeated_after_window", "same tokens"
                 if again == served else
                 f"other tokens (near-tied logits): {served} then {again}")
            stats_mem = devices[0].memory_stats() or {}
            note("memory", json.dumps({k: stats_mem.get(k) for k in (
                "bytes_limit", "peak_bytes_in_use", "bytes_in_use")}))
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell["chips"]])
            core = srv.core
            engine = {"max_num_seqs": core.cfg.max_num_seqs,
                      "num_kv_blocks": core.cfg.num_kv_blocks,
                      "kv_block_size": core.cfg.kv_block_size,
                      "prefill_buckets": buckets,
                      "preemptions": core.preemptions,
                      "attn_impl": core.statics.attn_impl}
    finally:
        await gen.stop()
    note("engine", json.dumps(engine))
    note("window", f"attempted={load['attempted']} failed={load['failed']} "
         f"completed={load['completed_in_window']} "
         f"in_flight_at_close={load['in_flight_at_close']} "
         f"offered_total={load['offered_total']} "
         f"drained_after_close_s={load['drained_after_close_s']:.2f}")
    note("generator_lateness_max_ms", f"{load['lateness_max_ms']:.3f}")
    note("programs_built_in_window", seen["programs_in_window"])
    note("flight_records", f"in_window={len(seen['flight'])} "
         f"before_the_profiler={len(seen['quiet_flight'])} "
         f"lost={seen['flight_lost']}")
    for failure in load["failures"]:
        note("failure", failure)
    cache.report("whole run")
    correct = bool(verdict["ok"] and after["ok"] and load["failed"] == 0
                   and seen["programs_in_window"] == 0)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell["chips"],
              "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": load["attempted"],
           "failed": load["failed"], "metrics": {}, "device": device}
    note_samples(load)
    if not trace:
        out["metrics"] = read_metrics(
            bench, cell["name"], "end_to_end",
            {"load": load, "seconds": seconds, "setup_s": seen["setup_s"]})
        return out
    reduction = reduce_trace(seen["traced"], seen["flight"])
    if not keep_trace:
        shutil.rmtree(seen["traced"]["dir"], ignore_errors=True)
    device["busy_s"] = reduction["busy_s"]
    device["window_s"] = reduction["window_s"]
    out["breakdown"] = {"device_ops": reduction["device_ops"],
                        "idle_gaps": reduction["idle_gaps"]}
    ctx = {"spans": seen["quiet_spans"], "flight": seen["quiet_flight"],
           "window_s": seen["quiet_s"], "trace": reduction,
           "memory": stats_mem, "engine": engine, "load": load,
           "costs": costs_module(load_config(bench, cell["config"]))}
    out["metrics"] = read_metrics(bench, cell["name"], "per_layer", ctx)
    return out


async def sweep(bench: dict, config_name: str, mix_name: str, rates: list,
                seconds: float, seed: int, cache: CacheWatch) -> None:
    """Builder only: one engine, windows at rising rates; prints a row per
    rate. The knee is the highest rate whose backlog does not grow."""
    flags, hf, mix, model_dir, _ = prepare(bench, config_name, mix_name)
    vocab = int(hf["vocab_size"])
    async with Server(model_dir, flags, seed & 0x7fffffff) as srv:
        await warm_up(srv, mix, vocab, seed)
        for rate in rates:
            gen = LoadGen()
            try:
                await gen.start({"base": srv.base, "model": MODEL_NAME,
                                 "mix": mix, "seed": seed, "seconds": seconds,
                                 "vocab": vocab, "rate_rps": rate})
                seen = await measure(srv, gen, mix, seconds, False, cache)
            finally:
                await gen.stop()
            load = seen["load"]
            note_samples(load)
            e2e = read_metrics(bench, None, "end_to_end",
                               {"load": load, "seconds": seconds,
                                "setup_s": seen["setup_s"]})
            decode = [r for r in seen["flight"] if r["kind"] == "decode"]
            fill = (statistics.fmean(r["batch_fill"] for r in decode)
                    if decode else 0.0)
            print(json.dumps({
                "rate_rps": rate,
                "completed_rps": load["completed_in_window"] / seconds,
                "attempted": load["attempted"], "failed": load["failed"],
                "backlog_at_close": load["in_flight_at_close"],
                "mean_batch_fill": fill,
                "preemptions": srv.core.preemptions,
                **{k: v["value"] for k, v in e2e.items()
                   if k != "setup_s"}}), flush=True)
            # let the engine drain what the closed connections left
            await asyncio.sleep(2.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", metavar="MIX")
    ap.add_argument("--config")
    ap.add_argument("--rates", default="")
    ap.add_argument("--keep-trace", action="store_true",
                    help="builder only: leave the profiler's files in "
                         ".bench_work/trace")
    opts = ap.parse_args(argv)
    bench = load_benchmark()
    seconds = opts.seconds or float(bench["run_seconds"])
    if opts.sweep:
        chips = 1
    else:
        cell = next((w for w in bench["workloads"]
                     if w["name"] == opts.workload), None)
        if cell is None:
            raise SystemExit(f"no cell named {opts.workload!r} in "
                             "BENCHMARK.json")
        chips = cell["chips"]
    devices = require_tpu(chips)
    note("setup[tpu ready]", f"{time.time() - T_PROCESS_START:.1f} s")
    sys.path.insert(0, ROOT)
    cache = enable_cache()
    from dynamo_tpu.runtime.log import setup_logging
    setup_logging(None)
    note("device", f"{devices[0].device_kind} x{len(devices)}")
    os.makedirs(WORK_DIR, exist_ok=True)
    if opts.sweep:
        rates = [float(r) for r in opts.rates.split(",") if r]
        asyncio.run(sweep(bench, opts.config, opts.sweep, rates, seconds,
                          opts.seed, cache))
        return 0
    out = asyncio.run(run_cell(bench, cell, opts.seed, seconds,
                               bool(opts.trace), devices, cache,
                               opts.keep_trace))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
