"""JAX engine adapter: EngineCore → AsyncEngine[PreprocessedRequest, ...].

The reference's engines translate BackendInput into vLLM/SGLang/TRT-LLM wire
protocols (lib/llm/src/engines/*); here the "engine" is in-process JAX, so
this adapter only maps the request, streams sampled tokens out of the slot
queue, and honors step-granular cancellation.
"""

from __future__ import annotations

from typing import AsyncIterator, Optional

from ...engine.config import EngineConfig, ModelConfig
from ...engine.core import FINISH_SENTINEL, EngineCore, EngineRequest
from ...engine.sampling import SlotSampling
from ...runtime.engine import AsyncEngine, ManyOut, ResponseStream, SingleIn
from ..protocols.annotated import Annotated
from ..protocols.common import BackendOutput, FinishReason, PreprocessedRequest


class JaxEngine(AsyncEngine):
    """Serves the engine-internal token protocol from an EngineCore."""

    def __init__(self, core: EngineCore):
        self.core = core

    @classmethod
    def from_model_dir(cls, model_dir: str,
                       engine_cfg: Optional[EngineConfig] = None,
                       load_weights: bool = True, **core_kwargs) -> "JaxEngine":
        model_cfg = ModelConfig.from_model_dir(model_dir)
        engine_cfg = engine_cfg or EngineConfig()
        params = None
        if load_weights:
            import jax.numpy as jnp

            # load_params_auto streams each device's shard straight from
            # disk when a mesh is given (host peak = one shard — the
            # 70B-scale path)
            from ...engine.weights import load_params_auto
            params = load_params_auto(
                model_dir, model_cfg, mesh=core_kwargs.get("mesh"),
                dtype=core_kwargs.get("param_dtype", jnp.bfloat16))
        return cls(EngineCore(model_cfg, engine_cfg, params=params,
                              **core_kwargs))

    def build_request(self, request: SingleIn) -> EngineRequest:
        pre: PreprocessedRequest = request.data
        sc = pre.stop_conditions
        # speculation knob: None = engine live default (spec_k = -1);
        # explicit values clamp to the compiled verify width at dispatch
        spec = getattr(pre, "speculation", None)
        return EngineRequest(
            rid=request.id,
            prompt=list(pre.token_ids),
            sampling=SlotSampling.from_options(pre.sampling_options),
            max_new_tokens=sc.max_tokens or 16384,
            eos_ids=frozenset(() if sc.ignore_eos else
                              (sc.stop_token_ids_hidden or pre.eos_token_ids)),
            ctx=request.ctx,
            spec_k=-1 if spec is None else max(0, int(spec)),
            # multi-tenant identity (llm/tenancy.py): payload fields
            # win, the wire-propagated context identity backs them up —
            # the KV tiers' per-tenant quota accounting keys on this
            tenant=(getattr(pre, "tenant_id", None)
                    or getattr(request.ctx, "tenant", None) or ""),
            session=getattr(pre, "session_id", None) or "",
        )

    async def generate(self, request: SingleIn) -> ManyOut:
        req = self.build_request(request)
        await self.core.submit(req)
        return self.stream_response(req, request)

    def stream_response(self, req: EngineRequest,
                        request: SingleIn) -> ManyOut:
        from ...runtime.tracing import current_trace
        trace = current_trace()

        async def stream() -> AsyncIterator[Annotated[BackendOutput]]:
            import asyncio

            emitted = 0
            while True:
                # bounded receive (DL007): the engine contract is that
                # every request ends in a FINISH sentinel (even loop
                # death routes through _fail_pending) — but a hung loop
                # must not hang this stream forever. Each timeout polls
                # the request's cancellation; a killed client's stream
                # ends instead of waiting on an engine that stopped
                # answering. The get_nowait fast path keeps the token
                # hot path free of wait_for's per-item task overhead.
                try:
                    item, payload = req.out_queue.get_nowait()
                except asyncio.QueueEmpty:
                    try:
                        item, payload = await asyncio.wait_for(
                            req.out_queue.get(), timeout=30.0)
                    except asyncio.TimeoutError:
                        if req.ctx is not None and req.ctx.is_killed:
                            return
                        continue
                if item is FINISH_SENTINEL:
                    reason: FinishReason = payload
                    if trace is not None:
                        # isl/osl + tenant/session ride the finish
                        # marker so collected traces are exportable as a
                        # replayable workload PRESERVING tenant and
                        # prefix-reuse structure (tools/fleetsim.py
                        # export-trace; ROADMAP sim item (d))
                        trace.event("engine.finish", reason=str(reason),
                                    isl=len(req.prompt), osl=emitted,
                                    tenant=req.tenant or None,
                                    session=req.session or None)
                    yield Annotated.from_data(BackendOutput.final(reason))
                    return
                token, logprob = item, payload
                emitted += 1
                yield Annotated.from_data(BackendOutput(
                    token_ids=[token], log_probs=[logprob],
                    cum_log_probs=None))

        return ResponseStream(stream(), request.ctx)
