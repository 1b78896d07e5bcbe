#!/usr/bin/env python3
"""The CPU rehearsal of the benchmark: everything but the chip.

    JAX_PLATFORMS=cpu python3 benchmark/selftest.py

It runs the harness end to end at tiny widths of every family that has a
fixture (``fixtures/tiny-*.json``: dense GQA, ``qwen2_moe`` with the gated
shared expert and unnormalised top-k, ``deepseek_v2`` with latent attention,
yarn rope and additive shared experts) through the same ``run_cell`` the
chip runs, and checks the yardstick's own arithmetic on known inputs. A new
family adds a fixture that names its reference (``"reference": "<name>"`` →
``references/<name>.py``) and edits nothing here. Numbers it prints are CPU
numbers: they show that the line parses, never how fast anything is.

``tests/`` may run the cheap parts one by one: every check but
``end_to_end`` takes no argument or a fixture's name, and raises on failure.
"""

from __future__ import annotations

import asyncio
import glob
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import loadgen  # noqa: E402
import run as bench_run  # noqa: E402
import stats  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TMP_METRIC = "selftest.tmp_metric"
# the mix of a fixture's served rehearsal: an open loop once, closed loops
# for the rest
REHEARSAL_MIX = {"tiny-dense": "selftest-open"}


def fixtures() -> list:
    """The tiny configurations, by the names of their files."""
    return sorted(os.path.splitext(os.path.basename(p))[0] for p in
                  glob.glob(os.path.join(HERE, "fixtures", "tiny-*.json")))


def load_fixture(name: str) -> dict:
    with open(os.path.join(HERE, "fixtures", f"{name}.json")) as f:
        return json.load(f)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"selftest failed: {what}")
    print(f"ok  {what}", flush=True)


def arithmetic() -> None:
    xs = list(range(1, 101))
    check(stats.percentile(xs, 95) == 95 and stats.percentile(xs, 50) == 50
          and stats.percentile([7], 95) == 7,
          "nearest-rank percentile on 1..100")
    check(stats.supported_tail(400) == 95.0
          and stats.supported_tail(100) == 90.0
          and stats.supported_tail(12) == 50.0,
          "the tail a sample supports: ten samples beyond it")
    check(abs(stats.iqr_spread([10, 11, 12, 13, 14, 15]) - 3.5 / 12.5) < 1e-12,
          "spread = (Q3-Q1)/median with statistics.quantiles")


def generator() -> None:
    mix = dict(traffic.load_mix("chat-open"), shuffle_block=8)
    a = traffic.schedule(mix, 7, 10, 1000)
    b = traffic.schedule(mix, 7, 10, 1000)
    c = traffic.schedule(mix, 2 ** 31 + 11, 10, 1000)
    check(a == b, "the same --seed gives the same arrivals, lengths and ids")
    check(a != c and len(a) == len(c)
          and sorted(len(r["prompt"]) for r in a)
          == sorted(len(r["prompt"]) for r in c)
          and sorted(r["max_tokens"] for r in a)
          == sorted(r["max_tokens"] for r in c),
          "another seed deals the same set of sizes in another order")
    at = lambda reqs: sum(1 for r in reqs if 8 <= r["due"] < 16)  # noqa: E731
    check(abs(at(a) - at(c)) <= 8 and a[3]["due"] != c[3]["due"]
          and abs(a[16]["due"] - c[16]["due"]) < 1e-9,
          "the order changes only inside blocks of 8: every seed offers "
          "the same work at the same pace")
    fixed = dict(mix, shuffle_block=1)
    d, e = (traffic.schedule(fixed, s, 10, 1000) for s in (7, 2 ** 31 + 11))
    check([(r["due"], len(r["prompt"]), r["max_tokens"]) for r in d]
          == [(r["due"], len(r["prompt"]), r["max_tokens"]) for r in e]
          and d[0]["prompt"] != e[0]["prompt"],
          "shuffle_block 1: the same arrivals and sizes for every seed, "
          "other token ids")
    n = round(mix["rate_rps"] * traffic.span_s(mix, 10))
    check(len(a) == n and a[-1]["due"] <= traffic.span_s(mix, 10),
          "an open loop offers rate x span requests inside the span")
    lens = [len(r["prompt"]) for r in a]
    spec = mix["prompt_tokens"]
    check(min(lens) >= spec["min"] and max(lens) <= spec["max"],
          "prompt lengths stay inside the mix's clip")
    closed = traffic.schedule(traffic.load_mix("prefill-closed"), 3, 10, 1000)
    check(all(r["due"] is None and r["max_tokens"] == 32 for r in closed),
          "a closed loop's requests carry no due time")
    check(traffic.buckets_used(mix, [128, 256, 512, 1024, 2048, 4096])
          == [128, 256, 512, 1024],
          "the buckets a mix can use")
    shared = dict(mix, shared_prefix={"groups": 2, "tokens": 40, "zipf": 1.0})
    reqs = traffic.schedule(shared, 5, 10, 1000)
    heads = {tuple(r["prompt"][:31]) for r in reqs}
    check(len(heads) == 2, "shared prefixes: requests fall into the groups")


def window_arithmetic() -> None:
    """The load generator's reduction on records with known answers."""
    run = loadgen.Run({"mix": {"loop": "open", "rate_rps": 1.0, "ramp_s": 1,
                               "prompt_tokens": {"dist": "const", "value": 4},
                               "output_tokens": {"dist": "const", "value": 3}},
                       "seconds": 10, "seed": 0, "vocab": 50})

    def rec(start, arrivals, asked=3, done=True, status=200, usage=3):
        return {"start": start, "first": arrivals[0] if arrivals else None,
                "arrivals": arrivals, "status": status, "asked": asked,
                "completion_tokens": usage, "done": done, "error": None}
    run.records = [
        rec(0.5, [0.9, 1.1, 1.2]),              # started in the ramp
        rec(2.0, [2.1, 2.2, 2.4]),              # wholly inside
        rec(10.5, [10.9, 11.2], done=False),    # first token inside
        rec(5.0, [], status=503),               # refused
        rec(6.0, [6.5, 6.6], usage=2),          # short answer
    ]
    run.lateness = [0.001, 0.004]
    out = run.reduce(11.5)
    check(out["attempted"] == 4 and out["failed"] == 2,
          "attempted = started in the window; refused and short answers fail")
    check([round(x) for x in out["ttft_ms"]] == [100, 400],
          "TTFT from when a request was due, no sample for a failed one")
    check(out["tokens_in_window"] == 8 and len(out["itl_ms"]) == 5,
          "tokens and gaps counted where they arrive inside the window")
    check(abs(out["lateness_max_ms"] - 4.0) < 1e-9, "worst lateness")


def trace_reduction() -> None:
    events = {"/device:TPU:0": [
        ("while", 0, 100), ("fusion.1", 0, 40), ("custom-call", 50, 40),
        ("copy", 95, 20), ("fusion.1", 200, 100), ("tail", 500, 10)]}
    out = trace_reduce.reduce(events, window=(0, 520),
                              host_spans=[("decode", 90, 210),
                                          ("prefill", 290, 400)])
    check(abs(out["busy_s"] - 225e-9) < 1e-15
          and abs(out["window_s"] - 520e-9) < 1e-15,
          "busy time is the union of op intervals; nested ops count once")
    ops = dict((n, round(s * 1e9)) for n, s in out["device_ops"])
    check(ops == {"fusion.1": 140, "custom-call": 40, "copy": 20,
                  "tail": 10},
          "the op table lists what ran inside an enclosing op, not the op")
    gaps = dict((n, round(s * 1e9)) for n, s in out["idle_gaps"])
    check(gaps == {"decode": 85, "prefill": 200, "none": 10},
          "idle gaps take the label of the host span that covers them")
    table = dict((n, (round(s * 1e9), c)) for n, s, c in out["ops"])
    check(table == {"fusion.1": (140, 2), "custom-call": (40, 1),
                    "copy": (20, 1), "tail": (10, 1)}
          and [row[:2] for row in out["ops"][:2]] == out["device_ops"][:2],
          "ops: every leaf op with its seconds and its count, longest first")
    check("scopes" not in out, "no scopes where no op carries an op_name")
    named = trace_reduce.reduce(events, window=(0, 520), scopes={
        "fusion.1": "decode/moe_mlp", "custom-call": "decode/attention",
        "copy": "decode/attention"})
    check(dict((n, round(s * 1e9)) for n, s in named["scopes"])
          == {"decode/moe_mlp": 140, "decode/attention": 60},
          "scopes: seconds per named-scope path of the ops that carry one")
    check(trace_reduce.scope_of(
        '%f.1 = bf16[8] fusion(%p), metadata={op_name="jit(decode_k)/decode'
        '/attention/dot_general" source_file="a.py"}') == "decode/attention"
        and trace_reduce.scope_of("%f.1 = bf16[8] fusion(%p)") is None,
        "an op's scope is its op_name without the program and the primitive")
    programs = trace_reduce.program_table({"/device:TPU:0": [
        ("jit_decode_k(123)", 0, 100), ("jit_prefill(7)", 100, 300),
        ("jit_decode_k(123)", 400, 100), ("jit_prefill(8)", 500, 50)]})
    check([(n, round(s * 1e9), c) for n, s, c in programs]
          == [("jit_prefill", 350, 2), ("jit_decode_k", 200, 2)],
          "programs: seconds and dispatches per program, fingerprints dropped")
    with open(os.path.join(HERE, "fixtures", "trace_events.json")) as f:
        fixture = json.load(f)
    got = trace_reduce.reduce(
        {k: [tuple(e) for e in v] for k, v in fixture["events"].items()})
    for key, want in fixture["expect"].items():
        check(abs(got[key] - want) <= 1e-9 * max(1.0, abs(want)),
              f"recorded chip trace reduces to the recorded {key}")
    n_events = sum(len(v) for v in fixture["events"].values())
    check(sum(c for _, _, c in got["ops"]) <= n_events
          and sum(s for _, s, _ in got["ops"]) <= got["busy_s"] * (1 + 1e-9)
          and got["ops"][0][:2] == got["device_ops"][0]
          and len(got["ops"]) > len(got["device_ops"]),
          "the recorded trace's op table: all its leaf ops, inside busy_s")


def reader_check() -> None:
    """``kernel.paged_attention_ms`` on a small recorded ``ctx``: seconds
    and dispatches of one traced run of ``qwen15-moe-a2.7b.decode-closed``
    (my chip run, PR 30, seed 3000000011; 12 layers a step), and a second
    kernel row made up to show that the kernel's variants add up."""
    read = bench_run.metric_reader("per_layer", "kernel.paged_attention_ms")
    trace = {"ops": [["%fusion.251 bf16[60,64,2816] fusion", 0.97852624, 2124],
                     ["%paged_attention.12 bf16[64,16,2048] custom-call "
                      "tpu_custom_call", 0.82311276, 2124],
                     ["%paged_attention.3 bf16[64,16,2048] custom-call "
                      "tpu_custom_call", 0.00088724, 2]],
             "programs": [["jit_decode_k", 2.765658133, 177],
                          ["jit_prefill", 0.420569207, 21]]}
    got = read({"trace": trace})
    check(abs(got - 0.824 / 177 * 1e3) < 1e-9,
          "kernel.paged_attention_ms: seconds of %paged_attention* over the "
          f"dispatches of jit_decode_k, in ms ({got:.3f})")
    check(read({"trace": {"ops": trace["ops"], "programs": []}}) is None
          and read({"trace": {"busy_s": 1.0, "window_s": 2.0}}) is None
          and read({"trace": dict(trace, ops=trace["ops"][:1])}) is None,
          "no decode dispatch or no such op in the trace: nothing to read")
    # the selection's two kernels by their names, in both configurations'
    # readers: per step as PERF.md §5 has them (PR 36's traced run of
    # deepseek-v3.2.docqa-closed, 85 steps of 7 layers: index scores 4.27
    # ms, the exact top-k 1.14, unseen until PR 45); a prefill chunk's
    # top-k for its 256 rows is the same kernel and not the step's
    trace = {"ops": [["%fusion.1473 bf16[131072,640] fusion", 1.18235, 595],
                     ["%index_scores.12 f32[64,17,1024] custom-call "
                      "tpu_custom_call", 0.36295, 595],
                     ["%dsa_select_compact.5 s32[64,2048] custom-call "
                      "tpu_custom_call", 0.0969, 595],
                     ["%dsa_select_compact.9 s32[256,2048] custom-call "
                      "tpu_custom_call", 0.0598, 91]],
             "programs": [["jit_decode_k", 3.329, 85],
                          ["jit_prefill", 1.105, 13]]}
    engine = {"max_num_seqs": 64}
    read = bench_run.metric_reader("per_layer", "kernel.dsa_select_ms")
    for costs in (costs_of("deepseek_v32"), costs_of("dots3_note")):
        got = read({"trace": trace, "engine": engine, "costs": costs})
        check(abs(got - 5.41) < 1e-9
              and abs(read({"trace": dict(trace, ops=trace["ops"][:2]),
                            "engine": engine, "costs": costs}) - 4.27) < 1e-9,
              f"kernel.dsa_select_ms priced by {costs.__name__}: "
              f"%index_scores* and %dsa_select_compact* with the decode "
              f"batch first, over the dispatches of jit_decode_k, in ms "
              f"({got:.2f})")


def cost_families() -> list:
    """The families that brought a costs module, by the files' names: one
    that a later PR adds is held out of every other family's stages with no
    edit here."""
    return sorted(os.path.basename(p)[:-len("_costs.py")] for p in glob.glob(
        os.path.join(HERE, "references", "*_costs.py")))


def costs_of(reference: str):
    """The costs module of the family whose reference is ``reference``, as
    ``run_cell`` puts it into the readers' ``ctx``."""
    return bench_run.costs_module({"reference": reference})


# One reader a kernel, whatever configuration's shapes it runs at (PR 58): a
# reader of these takes the cell's costs module from ``ctx["costs"]``. Per
# reader: the stage it asks by, the reader's unit over the module's (ms over
# seconds a step; a share is a share) and, per family that prices the stage
# today, the module's own call that the reader has to return.
def _stage_call(call: str, stage: str):
    return lambda costs, ctx: getattr(costs, call)(ctx, stage)


MERGED_READERS = {
    f"kernel.{stage}_{what}": (stage, scale, {
        family: _stage_call(call, stage) for family in families})
    for stage, families in (("dsa_select", ("deepseek_v32", "dots3_note")),
                            ("sparse_attention", ("deepseek_v32",
                                                  "dots3_note")),
                            ("gqa_full", ("mimo_v2", "exaone_moe")),
                            ("gqa_window", ("mimo_v2", "exaone_moe")),
                            ("ssd_step", ("granite_moe_hybrid",)),
                            ("ssd_chunk", ("granite_moe_hybrid",)))
    for what, call, scale in (("ms", "stage_seconds_per_step", 1e3),
                              ("roofline_pct", "stage_roofline_pct", 1))}
MERGED_READERS["kernel.mla_decode_roofline_pct"] = ("mla_decode", 1, {
    "kimi_k2": lambda costs, ctx: costs.decode_roofline_pct(ctx),
    "kimi_linear": lambda costs, ctx: costs.roofline_pct(ctx, "latent_read")})
# Kernel rows made up beside the recorded trace's ops (it is a dense model's:
# it holds none of these), the decode batch first, and the served programs'
# dispatches (``ssd_chunk`` is the prefill program's, every other kernel a
# decode program's); the counters a step's or a dispatch's cost is worked out
# from.
KERNEL_ROWS = [
    [f"%{kernel}.7 bf16[64,{8 + i},128] custom-call tpu_custom_call",
     0.04 * (i + 1), 40 * (i + 1)] for i, kernel in enumerate((
         "index_scores", "dsa_select_compact", "sparse_latent_attention",
         "gqa_full_read", "gqa_window_read", "paged_attention", "kda_step",
         "ssd_step", "ssd_chunk"))]
PROGRAMS = [["jit_decode_k", 0.9, 40], ["jit_decode_mtp", 0.8, 32],
            ["jit_prefill", 0.6, 12]]
DECODE_RECORD = {"kind": "decode", "K": 1, "batch_fill": 64, "rows": 128,
                 "ctx_tokens": 262144, "sel_tokens": 98304,
                 "win_tokens": 8192}
PREFILL_RECORD = {"kind": "prefill", "scan_tokens": 3000, "ssd_chunks": 24}


def family_engine(reference: str) -> dict:
    """What ``run_cell`` reports of the engine of the configuration of
    BENCHMARK.json that names ``reference``, from its deployment's flags (a
    costs module finds its configuration by them)."""
    bench = bench_run.load_benchmark()
    config = next(c for c in (bench_run.load_config(bench, e["name"])
                              for e in bench["configs"])
                  if c.get("reference") == reference)
    flags = config["deployment"]["flags"]
    return {key: int(flags[flags.index(f"--{key.replace('_', '-')}") + 1])
            for key in ("max_num_seqs", "num_kv_blocks", "kv_block_size")}


def merged_reader(name: str) -> None:
    """The reader ``name`` with ``ctx["costs"]`` = a family's costs module
    returns what that module's own function returns, for every family that
    prices its stage, and nothing under any other costs module. The share
    of a roofline is priced at the v5e's peaks here (the CPU is in no table
    of peaks): the arithmetic is what is held, the number is no chip's."""
    import peaks
    stage, scale, families = MERGED_READERS[name]
    read = bench_run.metric_reader("per_layer", name)
    check(reader_file(name) == f"{name}.py", f"{name}: a file of its own")
    trace = trace_reduce.reduce(fixture_events())
    trace = dict(trace, ops=trace["ops"] + KERNEL_ROWS,
                 programs=PROGRAMS)
    ctx = {"trace": trace, "flight": [DECODE_RECORD] * 3 + [PREFILL_RECORD]}
    real = peaks.roofline_s
    peaks.roofline_s = lambda flops, moved, kind, **kw: real(
        flops, moved, "TPU v5 lite", **kw)
    try:
        for family, own in families.items():
            costs = costs_of(family)
            cell = dict(ctx, engine=family_engine(family), costs=costs)
            got, want = read(cell), own(costs, cell)
            check(want is not None and want > 0 and got == want * scale,
                  f"{name} with ctx['costs'] = {costs.__name__}: what the "
                  f"module's own function returns ({got:.4f})")
        others = [None, costs_of(None)] + [
            costs_of(f) for f in cost_families() if f not in families]
        check(all(read(dict(ctx, engine={}, costs=c)) is None
                  for c in others) and read(dict(ctx, engine={})) is None,
              f"{name}: nothing to read where the cell's family prices no "
              f"{stage} stage, or brought no costs module")
    finally:
        peaks.roofline_s = real


PER_LAYER_LIMIT = 128       # the contract's
OPEN_LOOP_METRICS = ("ttft_p50_ms", "itl_p95_ms")


def reader_file(name: str):
    """The file under ``layer_metrics/`` that ``run.metric_reader`` loads
    for the ``per_layer`` entry ``name``; None where it finds none."""
    try:
        read = bench_run.metric_reader("per_layer", name)
    except FileNotFoundError:
        return None
    return os.path.basename(read.__code__.co_filename)


def reader_files() -> list:
    return sorted(os.path.basename(p) for p in glob.glob(
        os.path.join(HERE, "layer_metrics", "*.py")))


def named_reader_files(bench: dict) -> set:
    return {reader_file(e["name"]) for e in bench["per_layer"]}


def entry_cells(bench: dict, entry: dict) -> list:
    return entry.get("workloads", [w["name"] for w in bench["workloads"]])


def entry_faults(bench: dict, entry: dict) -> list:
    """What is wrong with one ``per_layer`` entry: no reader file behind
    its name, a listed cell that is none, or a cell that does not report
    the end-to-end metric the entry moves (``ttft_p50_ms`` / ``itl_p95_ms``:
    the open loops alone)."""
    name, moves = entry["name"], entry["moves"]
    faults = []
    if reader_file(name) is None:
        faults.append(f"{name}: no layer_metrics/ file reads it")
    cells = {w["name"]: w for w in bench["workloads"]}
    target = next(m for m in bench["end_to_end"] if m["name"] == moves)
    for cell in entry_cells(bench, entry):
        if cell not in cells:
            faults.append(f"{name}: lists {cell}, which is no cell")
            continue
        if cell not in entry_cells(bench, target):
            faults.append(f"{name}: {cell} does not report {moves}")
        if moves in OPEN_LOOP_METRICS:
            with open(os.path.join(HERE, "traffic",
                                   f"{cells[cell]['traffic']}.json")) as f:
                loop = json.load(f)["loop"]
            if loop != "open":
                faults.append(f"{name}: moves {moves} in the {loop} loop "
                              f"{cell}")
    return faults


def double_reports(bench: dict) -> list:
    """(reader file, cell, moves) reported under two entries: a copy. One
    quantity under two names is what filled ``per_layer`` to its limit by
    PR 42 (a ``model_config`` PR brings ``<metric>.<group>`` entries; the
    next ``benchmark`` PR folds them into the lists: README.md)."""
    seen, twice = {}, []
    for entry in bench["per_layer"]:
        file = reader_file(entry["name"])
        for cell in entry_cells(bench, entry):
            key = (file, cell, entry["moves"])
            if key in seen:
                twice.append(f"{file} in {cell} moving {entry['moves']}: "
                             f"{seen[key]} and {entry['name']}")
            seen[key] = entry["name"]
    return twice


def layout() -> None:
    """``BENCHMARK.json``'s ``per_layer`` against ``layer_metrics/``
    (``test_layout.py`` runs the same rules one entry and one file a
    case)."""
    bench = bench_run.load_benchmark()
    faults = [f for e in bench["per_layer"] for f in entry_faults(bench, e)]
    check(not faults, "every per_layer entry has its reader file, lists "
          f"cells that exist and that report what it moves {faults or ''}")
    named = named_reader_files(bench)
    idle = [f for f in reader_files() if f not in named]
    check(not idle, f"every file under layer_metrics/ is named by an entry "
          f"{idle or ''}")
    twice = double_reports(bench)
    check(not twice, "no reader is reported twice in one cell under the "
          f"same moves {twice or ''}")
    check(len(bench["per_layer"]) <= PER_LAYER_LIMIT,
          f"per_layer holds {len(bench['per_layer'])} entries of the "
          f"contract's {PER_LAYER_LIMIT}")


def reference_lookup() -> None:
    import reference
    check(bench_run.reference_module({"model_type": "mistral"}) is reference,
          "a configuration that names no reference gets reference.py")
    for name in fixtures():
        ref = bench_run.reference_module(load_fixture(name))
        check(callable(ref.logits_for) and callable(ref.breakages_for)
              and set(ref.breakages_for(bench_run.hf_config(
                  load_fixture(name)))) <= set(ref.BREAKAGES),
              f"{name}: its reference has logits_for, BREAKAGES and "
              f"breakages_for ({os.path.basename(ref.__file__)})")
    try:
        bench_run.reference_module({"reference": "no-such-family"})
    except SystemExit as e:
        check("no-such-family" in str(e),
              "an unknown reference name fails loudly, before anything runs")
    else:
        check(False, "an unknown reference name fails loudly")


def fixture_events() -> dict:
    with open(os.path.join(HERE, "fixtures", "trace_events.json")) as f:
        return {k: [tuple(e) for e in v]
                for k, v in json.load(f)["events"].items()}


async def rehearse(bench: dict, cell: dict, trace: bool, devices, cache):
    return await bench_run.run_cell(bench, cell, 2 ** 31 + 5, 6.0, trace,
                                    devices, cache)


def end_to_end() -> None:
    import jax
    devices = jax.devices()
    cache = bench_run.enable_cache()
    # the rehearsal leaves no CPU programs in the checkout's cache
    jax.config.update("jax_enable_compilation_cache", False)
    real = bench_run.load_benchmark()
    # every reader runs in both rehearsal cells, whatever cells it lists
    for group in ("end_to_end", "per_layer"):
        real[group] = [{k: v for k, v in m.items() if k != "workloads"}
                       for m in real[group]]
    bench = dict(real, configs=[
        {"name": n, "file": f"benchmark/fixtures/{n}.json"}
        for n in fixtures()],
        per_layer=real["per_layer"] + [
            {"name": TMP_METRIC, "unit": "1", "better": "higher",
             "source": "program_counter", "layer": "Engine step",
             "moves": "out_tokens_per_s"}])
    # a traced run on the CPU has no device plane: the reduction is given
    # the recorded chip events instead (its own check is trace_reduction)
    bench_run.reduce_trace = lambda traced, flight: trace_reduce.reduce(
        fixture_events())
    tmp = os.path.join(HERE, "layer_metrics", f"{TMP_METRIC}.py")
    with open(tmp, "w") as f:
        f.write("def read(ctx):\n    return float(len(ctx['flight']))\n")
    try:
        for config in fixtures():
            mix = REHEARSAL_MIX.get(config, "selftest-closed")
            cell = {"name": f"{config}.{mix}", "config": config,
                    "traffic": mix, "chips": 1}
            for trace in (False, True):
                out = asyncio.run(rehearse(bench, cell, trace, devices,
                                           cache))
                line = json.dumps(out)
                back = json.loads(line)
                check(RESULT_KEYS <= set(back) and back["correct"] is True
                      and back["failed"] == 0 and back["attempted"] > 0,
                      f"{cell['name']} trace={int(trace)}: the last line "
                      f"parses to the contract's keys and is correct")
                names = set(back["metrics"])
                if trace:
                    check(TMP_METRIC in names and "breakdown" in back
                          and {"busy_s", "window_s"} <= set(back["device"]),
                          "a new per-layer metric is found by its name "
                          "alone; the traced line has busy_s and window_s")
                    check("device.hbm_peak_pct" not in names,
                          "a reader with nothing to read (no memory stats "
                          "on the CPU) leaves its metric out")
                else:
                    check(names == {m["name"] for m in real["end_to_end"]},
                          "trace=0 reports the end-to-end metrics")
    finally:
        os.remove(tmp)


def reference_check(name: str) -> None:
    """The fixture's reference against the engine at tiny widths, and the
    breakages that the tolerance has to catch: those its module lists for
    this configuration."""
    import jax
    import numpy as np
    import reference
    from dynamo_tpu.engine.config import EngineConfig, ModelConfig
    from dynamo_tpu.engine.core import EngineCore

    config = load_fixture(name)
    ref = bench_run.reference_module(config)
    # deeper than the served rehearsal, so that one layer is a small part
    # of the whole, as on the chip
    hf = dict(bench_run.hf_config(config), num_hidden_layers=6)
    cfg = ModelConfig.from_hf_config(hf)
    core = EngineCore(cfg, EngineConfig(
        max_model_len=128, num_kv_blocks=32, max_num_seqs=2,
        quantization="int8", seed=11))
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab_size, size=48).tolist()
    # the engine's own greedy continuation, through its prefill and
    # decode programs (the HTTP leg is end_to_end's)
    ids, lps = greedy(core, prompt, 8)
    rep = reference.compare(core.params, hf, prompt, ids, lps,
                            forward=ref.logits_for)
    check(rep["ok"], f"{name}: engine within {reference.TOL_STD} std of "
          f"{os.path.basename(ref.__file__)} (logprob err "
          f"{rep['worst_logprob_err_std']:.3f}"
          f", argmax gap {rep['worst_argmax_gap_std']:.3f})")
    breakages = ref.breakages_for(hf)
    check(len(breakages) > 0, f"{name}: breakages to catch: {breakages}")
    for broken in breakages:
        rep = reference.compare(core.params, hf, prompt, ids, lps,
                                broken=broken, forward=ref.logits_for)
        check(not rep["ok"], f"{name}: {broken} trips the tolerance "
              f"(logprob err {rep['worst_logprob_err_std']:.2f} std, "
              f"argmax gap {rep['worst_argmax_gap_std']:.2f} std)")
    del core
    jax.clear_caches()


def greedy(core, prompt: list, n: int) -> tuple:
    """n greedy tokens and their logprobs from the engine's prefill and
    decode programs, driven directly (one sequence, slot 0)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from dynamo_tpu.engine.sampling import make_slot_keys
    cfg = core.cfg
    bucket = cfg.bucket_for(len(prompt))
    padded = np.zeros((bucket,), np.int32)
    padded[:len(prompt)] = prompt
    table = np.arange(1, core.M + 1, dtype=np.int32)
    f32, i32 = jnp.float32, jnp.int32
    tok, lp, core.kv = core._prefill_jit(
        core.params, core.kv, jnp.asarray(padded), jnp.asarray(table),
        jnp.asarray(0, i32), jnp.asarray(len(prompt), i32),
        jax.random.PRNGKey(0), jnp.asarray(0.0, f32), jnp.asarray(0, i32),
        jnp.asarray(1.0, f32))
    ids, lps = [int(tok)], [float(lp)]
    B = core.B
    tables = np.zeros((B, core.M), np.int32)
    tables[0] = table
    for step in range(n - 1):
        tokens = np.zeros((B,), np.int32)
        pos = np.zeros((B,), np.int32)
        tokens[0], pos[0] = ids[-1], len(prompt) + step
        keys = make_slot_keys(0, jnp.zeros((B,), jnp.int64),
                              jnp.zeros((B,), jnp.int64))
        toks, lpb, core.kv = core._decode_jit(
            core.params, core.kv, jnp.asarray(tokens), jnp.asarray(pos),
            jnp.asarray(tables), keys, jnp.zeros((B,), f32),
            jnp.zeros((B,), i32), jnp.ones((B,), f32))
        ids.append(int(toks[0]))
        lps.append(float(lpb[0]))
    return ids, lps


def main() -> int:
    arithmetic()
    generator()
    window_arithmetic()
    trace_reduction()
    reader_check()
    for name in MERGED_READERS:
        merged_reader(name)
    layout()
    reference_lookup()
    names = fixtures()
    check(len(names) >= 3, f"fixtures found by their file names: {names}")
    for name in names:
        reference_check(name)
    end_to_end()
    print("selftest: all passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
