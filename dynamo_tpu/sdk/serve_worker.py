"""Per-process service entry: resolve deps, run on-start hooks, serve
endpoints.

Reference: cli/serve_dynamo.py:44-190 — the per-watcher worker the circus
supervisor launches: ``@dynamo_worker`` builds the DistributedRuntime,
``component.create_service()``, binds the class instance, runs
``@async_on_start`` hooks, then blocks in ``serve_endpoint``."""

from __future__ import annotations

import argparse
import asyncio
import importlib
import logging
from typing import Any, AsyncIterator

from ..runtime.distributed import DistributedRuntime, Endpoint
from ..runtime.engine import (AsyncEngine, ManyOut, ResponseStream, SingleIn)
from .client import DependencyClient
from .config import ServiceConfig
from .service import DynamoService

logger = logging.getLogger("dynamo_tpu.sdk.worker")

__all__ = ["serve_service", "resolve_service"]


class _EndpointMethodEngine(AsyncEngine):
    """Adapts a bound async-generator endpoint method to AsyncEngine."""

    def __init__(self, fn):
        self.fn = fn

    async def generate(self, request: SingleIn) -> ManyOut:
        gen = self.fn(request.data)
        if hasattr(gen, "__aiter__"):
            stream = gen
        else:
            # plain coroutine → single-item stream
            async def one() -> AsyncIterator[Any]:
                yield await gen
            stream = one()
        return ResponseStream(stream, request.ctx)


def resolve_service(target: str) -> DynamoService:
    """``pkg.module:Attr`` → the DynamoService object."""
    mod_name, _, attr = target.partition(":")
    if not attr:
        raise SystemExit(f"service target must be module:Attr, got {target!r}")
    mod = importlib.import_module(mod_name)
    svc = getattr(mod, attr)
    if not isinstance(svc, DynamoService):
        raise SystemExit(f"{target} is not a @service")
    return svc


def find_in_graph(entry: DynamoService, name: str) -> DynamoService:
    for svc in entry.graph():
        if svc.name == name:
            return svc
    raise SystemExit(f"service {name!r} not reachable from {entry.name}")


async def serve_service(svc: DynamoService, runtime: DistributedRuntime
                        ) -> Any:
    """Bind + serve one service instance. Returns the instance (the caller
    owns the serve-forever wait)."""
    instance = svc.instantiate()
    # config injection (DYNAMO_SERVICE_CONFIG → instance.config) and the
    # runtime handle (the reference's @dynamo_worker passes the
    # DistributedRuntime into the service, cli/serve_dynamo.py:61-190) —
    # on-start hooks need it for KV event publishers, prefill queues, etc.
    instance.config = ServiceConfig.get_instance().for_service(svc.name)
    instance.runtime = runtime
    # dependency resolution
    for attr, dep in svc.dependencies.items():
        setattr(instance, attr,
                await DependencyClient.connect(runtime, dep.on))
    # on-start hooks (reference async_on_start: engine boot, metadata
    # publication, etc.)
    for hook in svc.on_start_hooks:
        await getattr(instance, hook)()
    # serve every endpoint
    for ep_name, attr in svc.endpoints.items():
        endpoint = Endpoint(runtime, svc.namespace, svc.name, ep_name)
        stats = getattr(instance, "stats_handler", None)
        await endpoint.serve(_EndpointMethodEngine(getattr(instance, attr)),
                             stats_handler=stats)
        logger.info("%s serving %s", svc.name, endpoint.path)
    return instance


async def amain(argv=None) -> None:
    p = argparse.ArgumentParser(prog="dynamo-tpu-serve-worker")
    p.add_argument("--target", required=True, help="graph module:Attr entry")
    p.add_argument("--service-name", required=True)
    p.add_argument("--runtime-server", required=True)
    p.add_argument("--verbose", "-v", action="store_true")
    args = p.parse_args(argv)
    from ..runtime.log import setup_logging
    setup_logging('debug' if args.verbose else None)
    entry = resolve_service(args.target)
    svc = find_in_graph(entry, args.service_name)
    runtime = await DistributedRuntime.connect(args.runtime_server)
    stop = asyncio.Event()
    drained = asyncio.Event()
    runtime.on_lease_lost = stop.set
    try:
        await serve_service(svc, runtime)
        # drain-to-exit (docs/planner.md): once EVERY endpoint this
        # process serves is draining and idle, exit cleanly (rc=0) — the
        # supervisor reaps a clean exit as retirement, not a crash

        def maybe_drained() -> None:
            if runtime._servers and all(s.draining and s.idle
                                        for s in runtime._servers):
                drained.set()

        for srv in runtime._servers:
            srv.on_drained = maybe_drained
        stop_t = asyncio.ensure_future(stop.wait())
        drain_t = asyncio.ensure_future(drained.wait())
        done, pending = await asyncio.wait(
            [stop_t, drain_t], return_when=asyncio.FIRST_COMPLETED)
        for t in pending:
            t.cancel()
        if drain_t in done:
            logger.info("all endpoints drained; retiring")
        else:
            # rc=1: a lost lease is a failure, not a retirement — the
            # supervisor must restart us (rc=0 is reserved for drain)
            logger.error("lease lost; exiting")
            raise SystemExit(1)
    finally:
        await runtime.shutdown()


def main() -> None:
    # workers build their EngineCore under this entry (examples/llm
    # components/worker.py, prefill_worker.py)
    from ..utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        pass


if __name__ == "__main__":
    main()
