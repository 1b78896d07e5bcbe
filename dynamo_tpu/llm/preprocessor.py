"""OpenAI → engine-internal preprocessing (and the backward delta path).

Reference: `OpenAIPreprocessor` (lib/llm/src/preprocessor.rs:63-303) plus the
prompt-template machinery (preprocessor/prompt/template/{oai,tokcfg,formatters}.rs):
render the HF chat template (jinja), tokenize, merge request sampling/stop
options with the model's EOS ids, optionally emit `token_ids` /
`formatted_prompt` annotations, and on the way back turn `BackendOutput`
deltas into OpenAI streaming chunks.

It is a pipeline :class:`Operator` on both the chat and completion types, so
`link(OpenAIPreprocessor(mdc), Backend(mdc), engine)` is a full OpenAI engine.
"""

from __future__ import annotations

import asyncio
import datetime
import os
from concurrent.futures import ThreadPoolExecutor
from typing import AsyncIterator, List, Optional

import jinja2
from prometheus_client import Counter

from ..runtime.engine import AsyncEngine, ManyOut, ResponseStream, SingleIn
from ..runtime.pipeline import Operator
from ..runtime.tracing import span
from .model_card import ModelDeploymentCard
from .protocols.annotated import Annotated
from .protocols.common import (BackendOutput, FinishReason, OutputOptions,
                               PreprocessedRequest, SamplingOptions,
                               StopConditions)
from .protocols.openai import (ChatCompletionRequest, ChatDeltaGenerator,
                               CompletionDeltaGenerator, CompletionRequest,
                               usage_dict)
from .tools import ToolCallingMatcher, ToolChoice

ANNOTATION_TOKEN_IDS = "token_ids"
ANNOTATION_FORMATTED_PROMPT = "formatted_prompt"

# A prompt of at least this many characters is encoded on a worker thread
# (`OpenAIPreprocessor._tokenize`): the encode of a 33k-token body holds the
# event loop, and with it a served engine's cycle, for 50 ms. The hop costs
# the request up to one cycle of the loop's wake-up, so a text whose inline
# encode stays under ~2 ms keeps to the loop's thread; that is where this
# tokenizer's encode passes 2 ms on the chip's host (CHANGES.md, PR 47).
OFFTHREAD_MIN_CHARS = 12_288

# how often the hop engages, process-wide; in no registry of its own: the
# HTTP front end registers it beside its other series (llm/http/metrics.py)
TOKENIZED_PROMPT_TOKENS = Counter(
    "nv_llm_http_service_tokenized_prompt_tokens",
    "Prompt tokens encoded, by the thread that encoded them",
    ["branch"], registry=None)
for _branch in ("inline", "offthread"):
    TOKENIZED_PROMPT_TOKENS.labels(_branch)

_tokenize_pool: Optional[ThreadPoolExecutor] = None


def _pool() -> ThreadPoolExecutor:
    global _tokenize_pool
    if _tokenize_pool is None:
        _tokenize_pool = ThreadPoolExecutor(
            max_workers=max(1, min(4, (os.cpu_count() or 2) - 1)),
            thread_name_prefix="tokenize")
    return _tokenize_pool


_FALLBACK_TEMPLATE = (
    "{% for message in messages %}"
    "<|{{ message.role }}|>\n{{ message.content }}\n"
    "{% endfor %}"
    "{% if add_generation_prompt %}<|assistant|>\n{% endif %}"
)


def _strftime_now(fmt: str) -> str:
    return datetime.datetime.now().strftime(fmt)


def _tojson(value, ensure_ascii: bool = False, indent=None, separators=None,
            sort_keys: bool = False) -> str:
    """transformers' chat-template tojson (plain json.dumps)."""
    import json
    return json.dumps(value, ensure_ascii=ensure_ascii, indent=indent,
                      separators=separators, sort_keys=sort_keys)


class PromptFormatter:
    """HF chat-template renderer (reference template/oai.rs + formatters.rs)."""

    def __init__(self, template: Optional[str], bos_token: str = "",
                 eos_token: str = ""):
        env = jinja2.Environment(
            loader=jinja2.BaseLoader(),
            trim_blocks=True, lstrip_blocks=True,
            extensions=["jinja2.ext.loopcontrols"])
        env.globals["raise_exception"] = self._raise
        env.globals["strftime_now"] = _strftime_now
        # HF's renderer uses plain json.dumps, NOT jinja's HTML-escaping
        # tojson — tool schemas with &, <, > must render identically to
        # apply_chat_template (tests/test_chat_template_conformance.py)
        env.filters["tojson"] = _tojson
        self._env = env
        self._template = env.from_string(template or _FALLBACK_TEMPLATE)
        self.bos_token = bos_token
        self.eos_token = eos_token

    @staticmethod
    def _raise(msg: str):
        raise jinja2.TemplateError(msg)

    def render(self, messages: List[dict], add_generation_prompt: bool = True,
               tools: Optional[List[dict]] = None, **extra) -> str:
        return self._template.render(
            messages=messages,
            add_generation_prompt=add_generation_prompt,
            bos_token=self.bos_token, eos_token=self.eos_token,
            tools=tools, **extra)


class OpenAIPreprocessor(Operator):
    """Chat/completions → PreprocessedRequest operator.

    forward: validate + render + tokenize + merge options
    backward: BackendOutput deltas → OpenAI chunks via the delta generators
    """

    def __init__(self, mdc: ModelDeploymentCard):
        self.mdc = mdc
        self.tokenizer = mdc.tokenizer()
        bos = ""
        if mdc.model_info.bos_token_id is not None:
            bos = self.tokenizer.id_to_token(mdc.model_info.bos_token_id) or ""
        eos = ""
        if mdc.model_info.eos_token_ids:
            eos = self.tokenizer.id_to_token(mdc.model_info.eos_token_ids[0]) or ""
        self.formatter = PromptFormatter(
            mdc.prompt_format.chat_template, bos_token=bos, eos_token=eos)

    # ------------------------------------------------------------------ fwd
    def preprocess_chat(self, req: ChatCompletionRequest) -> PreprocessedRequest:
        return self._common(
            req, self.tokenizer.encode_ids(self._chat_prompt(req)),
            req.effective_max_tokens())

    def _chat_prompt(self, req: ChatCompletionRequest) -> str:
        use_raw = bool(req.nvext and req.nvext.use_raw_prompt)
        if use_raw and len(req.messages) == 1:
            return req.messages[0].text()
        messages = []
        for m in req.messages:
            d = {"role": m.role, "content": m.text()}
            if m.name:
                d["name"] = m.name
            if m.tool_calls:
                d["tool_calls"] = m.tool_calls
            messages.append(d)
        return self.formatter.render(messages, tools=req.tools)

    def preprocess_completion(self, req: CompletionRequest) -> PreprocessedRequest:
        text = self._completion_text(req)
        return self._common(
            req, list(req.prompt) if text is None
            else self.tokenizer.encode_ids(text), req.max_tokens)

    @staticmethod
    def _completion_text(req: CompletionRequest) -> Optional[str]:
        """The prompt to tokenize; None for a pre-tokenized one."""
        if isinstance(req.prompt, str):
            return req.prompt
        if req.prompt and isinstance(req.prompt[0], int):
            return None
        raise ValueError("batch prompts must be fanned out before preprocessing")

    async def _tokenize(self, text: str) -> List[int]:
        """The prompt's ids, the same from both branches. A long text is
        encoded on a worker thread through the tokenizer's lock-releasing
        entry, and the event loop goes on meanwhile; a short one, and any
        text of a tokenizer kind that declares no such entry, is encoded
        here. A cancelled request leaves the worker to finish and drops
        its result; the worker's exception is raised here."""
        unlocked = getattr(self.tokenizer, "encode_ids_unlocked", None)
        offthread = unlocked is not None and len(text) >= OFFTHREAD_MIN_CHARS
        with span("tokenize", offthread=offthread) as s:
            if offthread:
                ids = await asyncio.get_running_loop().run_in_executor(
                    _pool(), unlocked, text)
            else:
                ids = self.tokenizer.encode_ids(text)
            if s is not None:
                s.attrs["tokens"] = len(ids)
        TOKENIZED_PROMPT_TOKENS.labels(
            "offthread" if offthread else "inline").inc(len(ids))
        return ids

    def _common(self, req, token_ids: List[int],
                max_tokens: Optional[int]) -> PreprocessedRequest:
        info = self.mdc.model_info
        budget = info.context_length - len(token_ids)
        if budget <= 0:
            raise ValueError(
                f"prompt length {len(token_ids)} exceeds model context "
                f"{info.context_length}")
        nvext = getattr(req, "nvext", None)
        ignore_eos = bool(nvext and nvext.ignore_eos)
        stop_conditions = StopConditions(
            max_tokens=min(max_tokens, budget) if max_tokens is not None else budget,
            stop=req.stop_list() or None,
            stop_token_ids_hidden=list(info.eos_token_ids),
            ignore_eos=ignore_eos,
        )
        stop_conditions.apply_ignore_eos()
        sampling = SamplingOptions(
            n=getattr(req, "n", 1) or 1,
            temperature=req.temperature,
            top_p=req.top_p,
            top_k=(nvext.top_k if nvext else None),
            seed=req.seed,
            frequency_penalty=req.frequency_penalty,
            presence_penalty=req.presence_penalty,
            repetition_penalty=(nvext.repetition_penalty if nvext else None),
            greedy=bool(nvext and nvext.greed_sampling),
        )
        # chat: `logprobs` is a bool + `top_logprobs` a count;
        # completions: `logprobs` IS the count.
        want = getattr(req, "logprobs", None)
        if isinstance(want, bool):
            n_logprobs = (getattr(req, "top_logprobs", None) or 1) if want else None
        else:
            n_logprobs = want
        output = OutputOptions(logprobs=n_logprobs)
        return PreprocessedRequest(
            token_ids=token_ids,
            stop_conditions=stop_conditions,
            sampling_options=sampling,
            output_options=output,
            eos_token_ids=list(info.eos_token_ids),
            mdc_sum=None,
            annotations=list((nvext.annotations if nvext else None) or []),
            # per-request draft budget (engine/spec/); None falls back
            # to the serving engine's live default
            speculation=(nvext.speculation if nvext else None),
            # multi-tenant plane (llm/tenancy.py): tenant/QoS/session
            # ride into the router's fair-share admission and the KV
            # tiers' quota accounting
            tenant_id=(nvext.tenant if nvext else None),
            qos=(nvext.priority if nvext else None),
            session_id=(nvext.session_id if nvext else None),
        )

    # ------------------------------------------------------------- operator
    async def generate(self, request: SingleIn, next_engine: AsyncEngine) -> ManyOut:
        req = request.data
        is_chat = ("messages" in req if isinstance(req, dict)
                   else isinstance(req, ChatCompletionRequest))
        with span("preprocess", chat=is_chat):
            if isinstance(req, dict):
                req = (ChatCompletionRequest if is_chat
                       else CompletionRequest).model_validate(req)
            if is_chat:
                formatted_prompt = self._chat_prompt(req)
                token_ids = await self._tokenize(formatted_prompt)
                max_tokens = req.effective_max_tokens()
            else:
                formatted_prompt = None
                text = self._completion_text(req)
                token_ids = (list(req.prompt) if text is None  # pre-tokenized
                             else await self._tokenize(text))
                max_tokens = req.max_tokens
            pre = self._common(req, token_ids, max_tokens)
        prompt_len = len(pre.token_ids)
        annotations: List[Annotated] = []
        if ANNOTATION_TOKEN_IDS in pre.annotations:
            annotations.append(Annotated.from_annotation(
                ANNOTATION_TOKEN_IDS, pre.token_ids))
        if is_chat and ANNOTATION_FORMATTED_PROMPT in pre.annotations:
            annotations.append(Annotated.from_annotation(
                ANNOTATION_FORMATTED_PROMPT, formatted_prompt))

        # Tool calling (reference preprocessor/tools.rs): when tools are in
        # play the full message must be inspected, so text is buffered and
        # either re-emitted verbatim or replaced by tool_calls at finish.
        # Validation happens BEFORE engine dispatch — a malformed request
        # must not leak an orphaned in-flight generation.
        matcher = None
        if is_chat:
            choice = ToolChoice(req.tool_choice,
                                has_tools=bool(req.tools))
            if choice.active and not req.tools:
                raise ValueError(
                    "tool_choice requires a non-empty tools list")
            if req.tools and choice.active:
                matcher = ToolCallingMatcher(choice)

        downstream = await next_engine.generate(request.transfer(pre))

        gen = (ChatDeltaGenerator(req.model, request_id=f"chatcmpl-{request.id}")
               if is_chat else
               CompletionDeltaGenerator(req.model, request_id=f"cmpl-{request.id}"))

        # engines report chosen-token logprobs unconditionally; the wire
        # only carries them when the client asked (OpenAI conformance)
        want_logprobs = pre.output_options.logprobs is not None

        async def backward() -> AsyncIterator[Annotated[dict]]:
            for ann in annotations:
                yield ann
            completion_tokens = 0
            finished = False
            buffered: List[str] = []
            buffered_logprobs: List[dict] = []

            def chat_end_chunks(reason: FinishReason) -> List[dict]:
                """Finish-time chunks for the chat path, applying the tool
                matcher to the buffered message when active. Raises
                ValueError when a required tool call is missing — but only
                for clean finishes: a cancelled or truncated generation is
                reported as its real finish reason, not a tool error."""
                chunks: List[dict] = []
                if matcher is not None:
                    full = "".join(buffered)
                    clean = reason in (FinishReason.EOS, FinishReason.STOP)
                    try:
                        calls = matcher.get_calls(full)
                    except ValueError:
                        if clean:
                            raise
                        calls = []
                    if calls:
                        chunks.append(gen.tool_calls_chunk(calls))
                        reason = FinishReason.TOOL_CALLS
                    elif full:
                        merged = None
                        if buffered_logprobs:
                            merged = {"content": [
                                e for lp in buffered_logprobs
                                for e in lp.get("content", [])]}
                        chunks.append(gen.text_chunk(full, logprobs=merged))
                chunks.append(gen.finish_chunk(reason))
                # Usage always rides the stream; the HTTP layer drops it for
                # SSE clients that didn't opt in, and the unary aggregator
                # folds it into the response.
                chunks.append(gen.usage_chunk(prompt_len, completion_tokens))
                return chunks

            def chat_end(reason: FinishReason):
                try:
                    return chat_end_chunks(reason)
                except ValueError as e:
                    return [Annotated.from_error(str(e))]

            async for item in downstream:
                if isinstance(item, Annotated):
                    if item.data is None:
                        yield item  # pass through errors/annotations
                        continue
                    out: BackendOutput = item.data
                else:
                    out = item
                completion_tokens += len(out.token_ids)
                text = out.text
                if text is None and out.tokens:
                    text = "".join(out.tokens)
                logprobs_payload = (_format_logprobs(out, is_chat)
                                    if want_logprobs else None)
                if matcher is not None and (text
                                            or logprobs_payload is not None):
                    # nothing escapes mid-buffer: empty-text deltas carrying
                    # logprobs are buffered too
                    if text:
                        buffered.append(text)
                    if logprobs_payload is not None:
                        buffered_logprobs.append(logprobs_payload)
                elif text:
                    yield Annotated.from_data(
                        gen.text_chunk(text, logprobs=logprobs_payload))
                elif logprobs_payload is not None:
                    yield Annotated.from_data(
                        gen.text_chunk("", logprobs=logprobs_payload))
                if out.finish_reason is not None:
                    finished = True
                    if is_chat:
                        for c in chat_end(out.finish_reason):
                            yield (c if isinstance(c, Annotated)
                                   else Annotated.from_data(c))
                    else:
                        yield Annotated.from_data(gen.finish_chunk(
                            out.finish_reason,
                            usage=usage_dict(prompt_len, completion_tokens)))
            if not finished and not request.ctx.is_killed:
                reason = (FinishReason.CANCELLED if request.ctx.is_stopped
                          else FinishReason.STOP)
                if is_chat:
                    for c in chat_end(reason):
                        yield (c if isinstance(c, Annotated)
                               else Annotated.from_data(c))
                else:
                    yield Annotated.from_data(gen.finish_chunk(
                        reason, usage=usage_dict(prompt_len, completion_tokens)))

        return ResponseStream(backward(), request.ctx)


def _format_logprobs(out: BackendOutput, is_chat: bool) -> Optional[dict]:
    if out.log_probs is None:
        return None
    if is_chat:
        content = []
        for i, lp in enumerate(out.log_probs):
            tok = (out.tokens[i] if out.tokens and i < len(out.tokens) else "")
            entry = {"token": tok, "logprob": lp, "top_logprobs": []}
            if out.top_logprobs and i < len(out.top_logprobs):
                entry["top_logprobs"] = [
                    {"token": str(t), "logprob": p}
                    for t, p in out.top_logprobs[i].items()]
            content.append(entry)
        return {"content": content}
    return {"token_logprobs": list(out.log_probs),
            "tokens": list(out.tokens or [])}
