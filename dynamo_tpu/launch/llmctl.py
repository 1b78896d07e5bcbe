"""Admin CLI: manage ModelEntry records and live disagg config in the KV
store. Reference: launch/llmctl (``llmctl http add chat-model <name>
<ns.comp.endpoint>`` → etcd ModelEntry, main.rs:81-210) plus a subcommand
for the disagg router's watched threshold (disagg_router.rs:38-140)."""

from __future__ import annotations

import argparse
import asyncio
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="llmctl")
    p.add_argument("--runtime-server", required=True,
                   help="discovery daemon host:port")
    sub = p.add_subparsers(dest="cmd", required=True)

    http = sub.add_parser("http", help="manage served models")
    hsub = http.add_subparsers(dest="http_cmd", required=True)
    add = hsub.add_parser("add")
    add.add_argument("kind", choices=["chat-model", "completion-model"])
    add.add_argument("name")
    add.add_argument("endpoint", help="dyn://ns/comp/ep or ns.comp.ep")
    rm = hsub.add_parser("remove")
    rm.add_argument("kind", choices=["chat-model", "completion-model"])
    rm.add_argument("name")
    hsub.add_parser("list")

    mdl = sub.add_parser("model", help="model registry cards "
                                       "(llm/registry.py — the multi-"
                                       "model serving plane's records)")
    msub = mdl.add_subparsers(dest="model_cmd", required=True)
    madd = msub.add_parser("add", help="register (or revise) a card; "
                                       "watching frontends start "
                                       "serving the name immediately")
    madd.add_argument("name")
    madd.add_argument("endpoint", help="dyn://ns/comp/ep or ns.comp.ep")
    madd.add_argument("--model-path", help="HF-style dir the frontend's "
                                           "preprocessor loads")
    madd.add_argument("--kv-block-size", type=int, default=16)
    madd.add_argument("--model-type", default="chat+completion",
                      choices=["chat", "completion", "chat+completion"])
    madd.add_argument("--geometry", default=None,
                      help='JSON geometry dict, e.g. \'{"tp": 8}\' — '
                           "feeds the derived program-set key")
    mrm = msub.add_parser("rm", help="remove a card; watching frontends "
                                     "drop the model (404 from then on)")
    mrm.add_argument("name")
    msub.add_parser("list")

    tn = sub.add_parser("tenant", help="multi-tenant policy admin "
                                       "(llm/tenancy.py): fair-share "
                                       "weights + per-tier KV quotas, "
                                       "applied live by watching "
                                       "workers/routers")
    tnsub = tn.add_subparsers(dest="tenant_cmd", required=True)
    tns = tnsub.add_parser("status", help="show the stored policy table")
    tns.add_argument("namespace", nargs="?")
    tnw = tnsub.add_parser("set-weight", help="fair-share weight (WDRR "
                                              "quantum scale)")
    tnw.add_argument("namespace")
    tnw.add_argument("tenant")
    tnw.add_argument("weight", type=float)
    tnq = tnsub.add_parser("set-quota", help="per-tier resident KV "
                                             "block quota (0 = "
                                             "unlimited); over-quota "
                                             "tenants' blocks evict "
                                             "first")
    tnq.add_argument("namespace")
    tnq.add_argument("tenant")
    tnq.add_argument("blocks", type=int)

    dis = sub.add_parser("disagg", help="live disagg-router config")
    dsub = dis.add_subparsers(dest="disagg_cmd", required=True)
    st = dsub.add_parser("set-threshold")
    st.add_argument("model")
    st.add_argument("value", type=int)

    pl = sub.add_parser("planner", help="dynamic planner admin "
                                        "(components/planner.py)")
    plsub = pl.add_subparsers(dest="planner_cmd", required=True)
    pst = plsub.add_parser("status", help="show planner state/decisions")
    pst.add_argument("namespace", nargs="?",
                     help="limit to one namespace (default: all)")
    pss = plsub.add_parser("set-slo", help="declare/update SLOs (merged "
                                           "into the stored record)")
    pss.add_argument("namespace")
    pss.add_argument("--ttft-p90-ms", type=float)
    pss.add_argument("--itl-p90-ms", type=float)
    pss.add_argument("--max-queue-depth", type=float)
    pss.add_argument("--slot-util-high", type=float)
    pss.add_argument("--slot-util-low", type=float)
    pss.add_argument("--kv-util-high", type=float)
    pss.add_argument("--min-decode-workers", type=int)
    pss.add_argument("--max-decode-workers", type=int)
    pss.add_argument("--max-local-prefill-length", type=int)
    pp = plsub.add_parser("pause", help="stop actuating (keep observing)")
    pp.add_argument("namespace")
    pr = plsub.add_parser("resume")
    pr.add_argument("namespace")

    sp = sub.add_parser("spec", help="speculative decoding admin "
                                     "(engine/spec/)")
    spsub = sp.add_subparsers(dest="spec_cmd", required=True)
    sps = spsub.add_parser("status", help="show stored draft budgets "
                                          "and live worker acceptance")
    sps.add_argument("namespace", nargs="?",
                     help="limit to one namespace (default: all)")
    spk = spsub.add_parser("set-k", help="set the live draft budget "
                                         "(clamped to each worker's "
                                         "compiled --spec-k maximum)")
    spk.add_argument("namespace")
    spk.add_argument("k", type=int)
    spo = spsub.add_parser("off", help="disable speculation live "
                                       "(equivalent to set-k 0)")
    spo.add_argument("namespace")

    kv = sub.add_parser("kv", help="KV tier admin (host/disk ladder; "
                                   "llm/kv/admin.py)")
    kvsub = kv.add_subparsers(dest="kv_cmd", required=True)
    kvs = kvsub.add_parser("status", help="show per-namespace host/disk "
                                          "tier occupancy and hit rates")
    kvs.add_argument("namespace", nargs="?",
                     help="limit to one namespace (default: all)")
    kvf = kvsub.add_parser("flush", help="persist host-resident KV to "
                                         "the disk tier NOW (the "
                                         "pre-restart barrier)")
    kvf.add_argument("namespace")
    kvf.add_argument("--clear", action="store_true",
                     help="drop the disk cache instead of persisting "
                          "into it")
    kvw = kvsub.add_parser(
        "set-weights",
        help="retune the router's per-tier overlap weights live "
             "(kv_router/scoring.py TIER_WEIGHTS): workers and routers "
             "watching kvtier/weights/{ns} apply the change without "
             "restart")
    kvw.add_argument("namespace")
    kvw.add_argument("--device", type=float, default=None)
    kvw.add_argument("--host", type=float, default=None)
    kvw.add_argument("--disk", type=float, default=None)
    kvw.add_argument("--remote", type=float, default=None)

    fl = sub.add_parser("faults", help="failpoint chaos drills "
                                       "(runtime/faults.py; docs/chaos.md)")
    flsub = fl.add_subparsers(dest="faults_cmd", required=True)
    fls = flsub.add_parser("set", help="arm one failpoint fleet-wide "
                                       "(merged into the stored table)")
    fls.add_argument("namespace")
    fls.add_argument("site", help="registered site, e.g. netstore.call")
    fls.add_argument("spec", help="[1-in-N,]error|delay:ms|torn|enospc")
    flc = flsub.add_parser("clear", help="disarm one site (or all with "
                                         "--all)")
    flc.add_argument("namespace")
    flc.add_argument("site", nargs="?")
    flc.add_argument("--all", action="store_true")
    flt = flsub.add_parser("status", help="show the stored failpoint "
                                          "table + the site catalog")
    flt.add_argument("namespace", nargs="?")

    tr = sub.add_parser("trace", help="fleet tracing admin "
                                      "(engine/flight_recorder.py)")
    trsub = tr.add_subparsers(dest="trace_cmd", required=True)
    trd = trsub.add_parser(
        "dump",
        help="collect every worker's engine flight-recorder ring "
             "(per-dispatch records: step kind, batch fill, device vs "
             "host-gap ms, KV tier hits, spec accept) + tracer stats")
    trd.add_argument("namespace")
    trd.add_argument("--last", type=int, default=32,
                     help="records per worker (default 32)")
    trd.add_argument("--timeout", type=float, default=5.0)
    trd.add_argument("--json", action="store_true",
                     help="print raw JSON dumps instead of a summary")

    dep = sub.add_parser("deployment",
                         help="manage graph deployments (deploy/ control "
                              "plane — the api-server CRUD over the store)")
    dpsub = dep.add_subparsers(dest="dep_cmd", required=True)
    dc = dpsub.add_parser("create")
    dc.add_argument("name")
    dc.add_argument("graph", help="module:ServiceClass")
    dc.add_argument("--config", help="service YAML path")
    dc.add_argument("--replicas", type=int, default=1)
    dc.add_argument("--max-restarts", type=int, default=None,
                    help="crash-restart cap per replica before the "
                         "deployment is marked failed (default: "
                         "controller default)")
    ds = dpsub.add_parser("scale")
    ds.add_argument("name")
    ds.add_argument("replicas", type=int)
    dt = dpsub.add_parser("terminate")
    dt.add_argument("name")
    dd = dpsub.add_parser("delete")
    dd.add_argument("name")
    dpsub.add_parser("list")
    return p


async def amain(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from ..runtime.distributed import DistributedRuntime
    runtime = await DistributedRuntime.connect(args.runtime_server)
    try:
        if args.cmd == "http":
            from ..llm.discovery import (ModelEntry, list_models,
                                         register_model, remove_model)
            kind = getattr(args, "kind", "").replace("-model", "")
            if args.http_cmd == "add":
                await register_model(runtime, ModelEntry(
                    name=args.name, endpoint=args.endpoint, model_type=kind))
                print(f"added {kind} model {args.name} → {args.endpoint}")
            elif args.http_cmd == "remove":
                ok = await remove_model(runtime, kind, args.name)
                print(f"{'removed' if ok else 'not found'}: {args.name}")
                return 0 if ok else 1
            else:
                entries = await list_models(runtime)
                if not entries:
                    print("(no models)")
                for key, e in sorted(entries.items()):
                    print(f"{e.model_type:11s} {e.name:30s} {e.endpoint}")
        elif args.cmd == "disagg":
            from ..llm.disagg import disagg_config_key
            import json
            await runtime.store.kv_put(
                disagg_config_key(args.model),
                json.dumps({"max_local_prefill_length": args.value}).encode())
            print(f"disagg threshold for {args.model} → {args.value}")
        elif args.cmd == "model":
            return await _model_cmd(runtime, args)
        elif args.cmd == "tenant":
            return await _tenant_cmd(runtime, args)
        elif args.cmd == "planner":
            return await _planner_cmd(runtime, args)
        elif args.cmd == "spec":
            return await _spec_cmd(runtime, args)
        elif args.cmd == "kv":
            return await _kv_cmd(runtime, args)
        elif args.cmd == "faults":
            return await _faults_cmd(runtime, args)
        elif args.cmd == "trace":
            return await _trace_cmd(runtime, args)
        elif args.cmd == "deployment":
            return await _deployment_cmd(runtime, args)
        return 0
    finally:
        await runtime.shutdown()


async def _model_cmd(runtime, args) -> int:
    """``llmctl model {add,list,rm}`` — registry cards on the kvstore
    (llm/registry.py). A frontend watching the registry starts/stops
    serving the name live; ``add`` on an existing name bumps its
    revision (frontends rebuild the pipeline)."""
    import json

    from ..llm.registry import (RegistryCard, list_cards, register_card,
                                remove_card)

    if args.model_cmd == "add":
        geometry = {}
        if args.geometry:
            try:
                geometry = json.loads(args.geometry)
            except ValueError as e:
                print(f"--geometry is not valid JSON: {e}", file=sys.stderr)
                return 1
            if not isinstance(geometry, dict):
                print("--geometry must be a JSON object", file=sys.stderr)
                return 1
        card = RegistryCard(name=args.name, endpoint=args.endpoint,
                            model_path=args.model_path,
                            model_type=args.model_type,
                            kv_block_size=args.kv_block_size,
                            geometry=geometry)
        await register_card(runtime, card)
        print(f"registered card {args.name} → {args.endpoint} "
              f"(program_set {card.program_set}, rev {card.revision})")
        return 0
    if args.model_cmd == "rm":
        ok = await remove_card(runtime, args.name)
        print(f"{'removed' if ok else 'not found'}: {args.name}")
        return 0 if ok else 1
    cards = await list_cards(runtime)
    if not cards:
        print("(no registry cards)")
    for name, c in sorted(cards.items()):
        print(f"{name:28s} {c.endpoint:32s} {c.model_type:16s} "
              f"bs={c.kv_block_size} prog={c.program_set} rev={c.revision}")
    return 0


async def _tenant_cmd(runtime, args) -> int:
    """``llmctl tenant`` — the tenant/control/{ns} policy table
    (llm/tenancy.py): every watching worker/router applies updates
    live (fair-share weights feed the WDRR admission; quotas feed the
    tiers' eviction preference)."""
    from ..llm.tenancy import TenantTable, tenant_control_key

    if args.tenant_cmd == "status":
        prefix = (tenant_control_key(args.namespace)
                  if args.namespace else "tenant/control/")
        entries = await runtime.store.kv_get_prefix(prefix)
        if not entries:
            print("(no tenant policies stored)")
            return 1
        for e in sorted(entries, key=lambda x: x.key):
            ns = e.key.rsplit("/", 1)[-1]
            try:
                table = TenantTable.from_json(e.value)
            except ValueError:
                print(f"namespace {ns}  (malformed table)")
                continue
            print(f"namespace {ns}")
            for t, pol in sorted(table.policies.items()):
                quota = (pol.kv_quota_blocks
                         if pol.kv_quota_blocks else "unlimited")
                print(f"  {t:20s} weight={pol.weight:g} "
                      f"kv_quota={quota} qos={pol.qos}")
        return 0
    key = tenant_control_key(args.namespace)
    entry = await runtime.store.kv_get(key)
    table = TenantTable()
    if entry is not None:
        try:
            table = TenantTable.from_json(entry.value)
        except ValueError:
            pass
    if args.tenant_cmd == "set-weight":
        if args.weight <= 0:
            print("weight must be > 0", file=sys.stderr)
            return 1
        pol = table.set(args.tenant, weight=args.weight)
    else:   # set-quota
        if args.blocks < 0:
            print("quota must be >= 0 (0 = unlimited)", file=sys.stderr)
            return 1
        pol = table.set(args.tenant, kv_quota_blocks=args.blocks)
    await runtime.store.kv_put(key, table.to_json())
    print(f"tenant {args.tenant} in {args.namespace}: "
          f"weight={pol.weight:g} kv_quota={pol.kv_quota_blocks} "
          f"qos={pol.qos}")
    return 0


async def _planner_cmd(runtime, args) -> int:
    """Planner admin over the planner/* KV keys (llm/slo.py layout): the
    planner watches slo/control live; status is its published snapshot."""
    import dataclasses
    import json

    from ..llm.slo import (PLANNER_PREFIX, ServiceLevelObjective,
                           control_key, slo_key)

    if args.planner_cmd == "status":
        prefix = (f"{PLANNER_PREFIX}status/{args.namespace}"
                  if args.namespace else f"{PLANNER_PREFIX}status/")
        entries = await runtime.store.kv_get_prefix(prefix)
        if not entries:
            print("(no planner status published)")
            return 1
        for e in entries:
            s = json.loads(e.value)
            ns = e.key.rsplit("/", 1)[-1]
            print(f"namespace {ns}  endpoint={s.get('endpoint')}  "
                  f"paused={s.get('paused')}")
            sig = s.get("signals") or {}
            workers = s.get("workers") or {}
            print(f"  workers: {len(workers.get('live', []))} live, "
                  f"draining={workers.get('draining', [])}")
            print(f"  signals: queue={sig.get('queue_depth', 0):.2f} "
                  f"slot_util={sig.get('slot_util', 0):.2f} "
                  f"kv_util={sig.get('kv_util', 0):.2f} "
                  f"ttft_p90={sig.get('ttft_p90_ms')}ms")
            print(f"  disagg_threshold: {s.get('disagg_threshold')}")
            print(f"  last decision: {s.get('last_decision')}")
            print(f"  counters: {s.get('counters')}")
            print(f"  slo: {s.get('slo')}")
        return 0
    if args.planner_cmd == "set-slo":
        entry = await runtime.store.kv_get(slo_key(args.namespace))
        slo = (ServiceLevelObjective.from_json(entry.value)
               if entry is not None else ServiceLevelObjective())
        for field in dataclasses.fields(ServiceLevelObjective):
            v = getattr(args, field.name, None)
            if v is not None:
                setattr(slo, field.name, v)
        await runtime.store.kv_put(slo_key(args.namespace), slo.to_json())
        print(f"slo for {args.namespace}: {dataclasses.asdict(slo)}")
        return 0
    # pause / resume
    paused = args.planner_cmd == "pause"
    await runtime.store.kv_put(
        control_key(args.namespace),
        json.dumps({"paused": paused}).encode())
    print(f"planner {args.planner_cmd}d for {args.namespace}")
    return 0


async def _spec_cmd(runtime, args) -> int:
    """Speculative-decoding admin over the spec/config/* KV keys
    (engine/spec/admin.py): workers watch their namespace's key
    (launch/run.py _wire_spec_config) and retune spec_k_live without a
    restart — mirroring the planner admin surface."""
    from ..engine.spec import SPEC_PREFIX, SpecConfig, spec_config_key

    if args.spec_cmd == "status":
        prefix = (spec_config_key(args.namespace)
                  if args.namespace else f"{SPEC_PREFIX}config/")
        entries = await runtime.store.kv_get_prefix(prefix)
        if not entries:
            print("(no spec config stored)")
            return 1
        for e in sorted(entries, key=lambda x: x.key):
            ns = e.key.rsplit("/", 1)[-1]
            try:
                cfg = SpecConfig.from_json(e.value)
            except ValueError:
                print(f"namespace {ns}  (malformed config)")
                continue
            state = "off" if cfg.k == 0 else f"k={cfg.k}"
            print(f"namespace {ns}  speculation {state}")
        return 0
    k = args.k if args.spec_cmd == "set-k" else 0
    if k < 0:
        print("k must be >= 0", file=sys.stderr)
        return 1
    await runtime.store.kv_put(spec_config_key(args.namespace),
                               SpecConfig(k=k).to_json())
    print(f"speculation for {args.namespace} → "
          f"{'off' if k == 0 else f'k={k}'}")
    return 0


async def _kv_cmd(runtime, args) -> int:
    """KV tier admin over the kvtier/* keys (llm/kv/admin.py): workers
    publish status snapshots and watch the control key; flush makes them
    persist host-resident blocks into the disk (G3) tier — the barrier
    to run before a planned restart so the warm start is complete."""
    import json
    import time

    from ..llm.kv.admin import (KV_PREFIX, KvTierStatus, kv_control_key,
                                kv_status_key, kv_weights_key)

    if args.kv_cmd == "set-weights":
        weights = {t: getattr(args, t) for t in ("device", "host", "disk",
                                                 "remote")
                   if getattr(args, t) is not None}
        if not weights:
            print("nothing to set (pass --device/--host/--disk/--remote)")
            return 1
        await runtime.store.kv_put(kv_weights_key(args.namespace),
                                   json.dumps(weights).encode())
        print(f"kv tier weights for {args.namespace} → {weights}")
        return 0
    if args.kv_cmd == "status":
        prefix = (kv_status_key(args.namespace)
                  if args.namespace else f"{KV_PREFIX}status/")
        entries = await runtime.store.kv_get_prefix(prefix)
        if not entries:
            print("(no kv tier status published)")
            return 1
        for e in sorted(entries, key=lambda x: x.key):
            try:
                s = KvTierStatus.from_json(e.value)
            except (ValueError, KeyError):
                print(f"{e.key}  (malformed status)")
                continue
            print(f"namespace {s.namespace}")
            print(f"  host:  {s.host_blocks}/{s.host_capacity} blocks  "
                  f"hit_rate={s.host_hit_rate:.3f}  "
                  f"offload_dropped={s.offload_dropped}")
            if s.disk_capacity:
                print(f"  disk:  {s.disk_blocks}/{s.disk_capacity} blocks "
                      f"({s.disk_bytes / 1e6:.1f} MB)  "
                      f"hit_rate={s.disk_hit_rate:.3f}  "
                      f"spill_dropped={s.spill_dropped}  "
                      f"onboards={s.disk_onboards}  dir={s.disk_dir}")
            else:
                print("  disk:  (tier off)")
            if s.remote_capacity or s.remote_blocks or s.remote_peer_blocks:
                print(f"  remote: {s.remote_blocks} object blocks"
                      f"{f'/{s.remote_capacity}' if s.remote_capacity else ''}"
                      f"  peers hold {s.remote_peer_blocks}  "
                      f"hit_rate={s.remote_hit_rate:.3f}  "
                      f"onboards={s.remote_onboards}  "
                      f"fetch_failures={s.remote_fetch_failures}  "
                      f"link={s.remote_link_gbps:.2f}GB/s "
                      f"rtt={s.remote_link_rtt_s * 1e3:.1f}ms")
        return 0
    # flush [--clear]
    await runtime.store.kv_put(
        kv_control_key(args.namespace),
        json.dumps({"flush": time.time(),
                    "clear": bool(args.clear)}).encode())
    print(f"kv {'clear' if args.clear else 'flush'} requested for "
          f"{args.namespace}")
    return 0


async def _faults_cmd(runtime, args) -> int:
    """``llmctl faults`` — arm/disarm deterministic failpoints
    fleet-wide over the faults/control/{ns} key (runtime/faults.py;
    every worker's watch_faults_loop applies the stored table live).
    Specs are validated HERE so a typo'd drill fails at the CLI, not
    silently fault-free on the fleet."""
    import json

    from ..runtime.faults import SITES, faults_control_key, parse_spec

    if args.faults_cmd == "status":
        prefix = (faults_control_key(args.namespace)
                  if args.namespace else "faults/control/")
        entries = await runtime.store.kv_get_prefix(prefix)
        if not entries:
            print("(no failpoints armed)")
        for e in sorted(entries, key=lambda x: x.key):
            ns = e.key.rsplit("/", 1)[-1]
            try:
                table = json.loads(e.value)
            except ValueError:
                print(f"namespace {ns}  (malformed table)")
                continue
            print(f"namespace {ns}")
            for site, spec in sorted(table.items()):
                print(f"  {site:26s} {spec}")
        print("\nregistered sites:")
        for site, desc in sorted(SITES.items()):
            print(f"  {site:26s} {desc}")
        return 0

    key = faults_control_key(args.namespace)
    entry = await runtime.store.kv_get(key)
    table = {}
    if entry is not None:
        try:
            table = json.loads(entry.value)
        except ValueError:
            table = {}
    if args.faults_cmd == "set":
        if args.site not in SITES:
            print(f"unknown site {args.site!r} (llmctl faults status "
                  f"lists the catalog)", file=sys.stderr)
            return 1
        try:
            parse_spec(args.site, args.spec)
        except ValueError as e:
            print(str(e), file=sys.stderr)
            return 1
        table[args.site] = args.spec
        await runtime.store.kv_put(key, json.dumps(table).encode())
        print(f"armed {args.site}={args.spec} for {args.namespace}")
        return 0
    # clear
    if args.all:
        table = {}
    elif args.site:
        table.pop(args.site, None)
    else:
        print("pass a site or --all", file=sys.stderr)
        return 1
    await runtime.store.kv_put(key, json.dumps(table).encode())
    print(f"faults table for {args.namespace}: {table or '(clear)'}")
    return 0


async def _trace_cmd(runtime, args) -> int:
    """``llmctl trace dump``: write the trace/control/{ns} key; every
    worker watching it (launch/run.py _wire_tracing) publishes its
    flight-recorder ring under trace/dump/{ns}/{worker:x} within its
    lease; collect and print (engine/flight_recorder.py key layout)."""
    import asyncio as _asyncio
    import json
    import time

    from ..engine.flight_recorder import trace_control_key, trace_dump_key

    requested_at = time.time()
    await runtime.store.kv_put(
        trace_control_key(args.namespace),
        json.dumps({"dump": requested_at, "last": args.last}).encode())
    prefix = trace_dump_key(args.namespace, 0).rsplit("/", 1)[0] + "/"
    deadline = time.monotonic() + args.timeout
    dumps = {}
    while time.monotonic() < deadline:
        for e in await runtime.store.kv_get_prefix(prefix):
            try:
                d = json.loads(e.value)
            except ValueError:
                continue
            if d.get("at", 0) >= requested_at:
                dumps[e.key] = d
        if dumps:
            # one settle pass so stragglers land, then report
            await _asyncio.sleep(0.3)
            for e in await runtime.store.kv_get_prefix(prefix):
                try:
                    d = json.loads(e.value)
                except ValueError:
                    continue
                if d.get("at", 0) >= requested_at:
                    dumps[e.key] = d
            break
        await _asyncio.sleep(0.1)
    if not dumps:
        print(f"(no worker answered the trace dump in {args.timeout:g}s "
              f"— is anything serving namespace {args.namespace!r}?)")
        return 1
    if args.json:
        print(json.dumps(list(dumps.values()), indent=2))
        return 0
    for key in sorted(dumps):
        d = dumps[key]
        fl = d.get("flight") or {}
        tr = d.get("tracer") or {}
        print(f"worker {d.get('worker_id')}  records={fl.get('ring', 0)}"
              f"/{fl.get('records_total', 0)}  "
              f"loop_lag={fl.get('loop_lag_ms', 0):.1f}ms "
              f"(max {fl.get('loop_lag_max_ms', 0):.1f}ms)  "
              f"built={fl.get('built', 0)} "
              f"({fl.get('built_ms', 0) / 1e3:.1f}s, "
              f"{fl.get('cache_misses', 0)} compiled)  "
              f"traces={tr.get('completed', 0)} "
              f"log_dropped={tr.get('dropped_log_lines', 0)}")
        for r in d.get("records", []):
            extra = {k: v for k, v in r.items() if k not in ("kind", "t")}
            print(f"  {r['kind']:8s} {extra}")
    return 0


async def _deployment_cmd(runtime, args) -> int:
    """Deployment CRUD straight against the store (the controller watches
    it; works whether the REST api-server is running or not). Updates go
    through the shared CAS helper — the api-server is a concurrent writer
    in another process, so plain read-modify-write would lose races."""
    import json
    import time

    from ..deploy.spec import (SPEC_PREFIX, STATUS_PREFIX, DeploymentSpec,
                               update_spec, validate_spec)

    if args.dep_cmd == "create":
        err = validate_spec(args.name, args.replicas,
                            max_restarts=args.max_restarts)
        if err:
            print(err, file=sys.stderr)
            return 1
        spec = DeploymentSpec(name=args.name, graph=args.graph,
                              config=args.config, replicas=args.replicas,
                              created_at=time.time(),
                              max_restarts=args.max_restarts)
        if not await runtime.store.kv_create(spec.key(), spec.to_json()):
            print(f"deployment {args.name!r} already exists", file=sys.stderr)
            return 1
        print(f"created deployment {args.name} ({args.graph} "
              f"x{args.replicas})")
    elif args.dep_cmd in ("scale", "terminate"):
        want = args.replicas if args.dep_cmd == "scale" else 0
        err = validate_spec(args.name, want)
        if err:
            print(err, file=sys.stderr)
            return 1

        def mutate(spec: DeploymentSpec):
            spec.replicas = want
            return None

        spec = await update_spec(runtime.store, args.name, mutate)
        if spec is None:
            print(f"not found: {args.name}", file=sys.stderr)
            return 1
        print(f"{args.dep_cmd}d {args.name} → replicas={spec.replicas}")
    elif args.dep_cmd == "delete":
        if not await runtime.store.kv_delete(SPEC_PREFIX + args.name):
            print(f"not found: {args.name}", file=sys.stderr)
            return 1
        print(f"deleted {args.name}")
    else:   # list
        specs = await runtime.store.kv_get_prefix(SPEC_PREFIX)
        statuses = {e.key[len(STATUS_PREFIX):]: json.loads(e.value)
                    for e in await runtime.store.kv_get_prefix(STATUS_PREFIX)}
        if not specs:
            print("(no deployments)")
        for e in sorted(specs, key=lambda x: x.key):
            spec = DeploymentSpec.from_json(e.value)
            status = statuses.get(spec.name, {})
            print(f"{spec.name:24s} {spec.graph:40s} "
                  f"replicas={spec.replicas} gen={spec.generation} "
                  f"state={status.get('state', '?')} "
                  f"ready={status.get('ready_replicas', '?')}")
    return 0


def main() -> None:
    sys.exit(asyncio.run(amain()))


if __name__ == "__main__":
    main()
