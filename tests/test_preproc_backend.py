"""Preprocessor + detokenizing Backend tests against the tiny trained
tokenizer (reference analogs: lib/llm/tests/preprocessor.rs snapshot tests,
backend.rs in-module Decoder tests)."""

import os

import pytest

from dynamo_tpu.llm.backend import Backend, Decoder, StopTrigger
from dynamo_tpu.llm.model_card import ModelDeploymentCard
from dynamo_tpu.llm.preprocessor import OpenAIPreprocessor
from dynamo_tpu.llm.protocols.annotated import Annotated
from dynamo_tpu.llm.protocols.common import BackendOutput, FinishReason
from dynamo_tpu.llm.protocols.openai import (ChatCompletionRequest,
                                             CompletionRequest)
from dynamo_tpu.runtime import Context, link
from tests.fixtures import RecordingEngine


@pytest.fixture(scope="module")
def mdc(request):
    tiny = request.getfixturevalue("tiny_model_dir")
    return ModelDeploymentCard.from_local_path(tiny, display_name="tiny")


def test_mdc_from_local_path(mdc):
    assert mdc.model_info.eos_token_ids, "eos ids read from config.json"
    assert mdc.prompt_format.chat_template
    assert mdc.mdcsum() == mdc.mdcsum()
    tk = mdc.tokenizer()
    ids = tk.encode("hello world").ids
    assert ids and tk.decode(ids) == "hello world"


def test_mdc_json_roundtrip(mdc, tmp_path):
    p = tmp_path / "mdc.json"
    mdc.save(str(p))
    loaded = ModelDeploymentCard.load(str(p))
    assert loaded.mdcsum() == mdc.mdcsum()


def test_chat_template_rendering(mdc):
    pre = OpenAIPreprocessor(mdc)
    req = ChatCompletionRequest(model="tiny", messages=[
        {"role": "system", "content": "be brief"},
        {"role": "user", "content": "hello world"},
    ])
    out = pre.preprocess_chat(req)
    text = mdc.tokenizer().decode(out.token_ids, skip_special_tokens=False)
    assert "<|system|>" in text and "<|user|>" in text
    assert text.endswith("<|assistant|>")
    assert out.stop_conditions.stop_token_ids_hidden == mdc.model_info.eos_token_ids


def test_preprocess_merges_options(mdc):
    pre = OpenAIPreprocessor(mdc)
    req = ChatCompletionRequest(
        model="tiny", messages=[{"role": "user", "content": "hi"}],
        max_tokens=7, temperature=0.5, stop=["END"], seed=3,
        nvext={"ignore_eos": True})
    out = pre.preprocess_chat(req)
    assert out.stop_conditions.max_tokens == 7
    assert out.stop_conditions.stop == ["END"]
    assert out.stop_conditions.stop_token_ids_hidden == []  # ignore_eos
    assert out.sampling_options.temperature == 0.5
    assert out.sampling_options.seed == 3


def test_preprocess_completion_pretokenized(mdc):
    pre = OpenAIPreprocessor(mdc)
    req = CompletionRequest(model="tiny", prompt=[5, 6, 7], max_tokens=2)
    out = pre.preprocess_completion(req)
    assert out.token_ids == [5, 6, 7]


def test_context_overflow_rejected(mdc):
    pre = OpenAIPreprocessor(mdc)
    huge = "word " * 5000
    with pytest.raises(ValueError):
        pre.preprocess_chat(ChatCompletionRequest(
            model="tiny", messages=[{"role": "user", "content": huge}]))


# ---------------------------------------------------------------- decoder


def test_decoder_incremental_roundtrip(mdc):
    tk = mdc.tokenizer()
    text = "señor açaí over the lazy dog 日本語"
    ids = tk.encode(text).ids
    dec = Decoder(tk)
    got = "".join(r.text for r in map(dec.step, ids) if r.text)
    assert got == text


def test_decoder_hidden_stop_token(mdc):
    tk = mdc.tokenizer()
    eos = mdc.model_info.eos_token_ids[0]
    dec = Decoder(tk, hidden_stop_ids=[eos])
    ids = tk.encode("hello world").ids
    for tid in ids:
        assert dec.step(tid).stop_trigger is None
    res = dec.step(eos)
    assert res.stop_trigger is StopTrigger.HIDDEN_STOP_TOKEN
    assert res.text is None  # hidden: no text surfaced for the EOS


def test_decoder_stop_sequence_is_swallowed(mdc):
    tk = mdc.tokenizer()
    dec = Decoder(tk, stop_sequences=["lazy"])
    ids = tk.encode("the quick lazy dog").ids
    out, trigger = [], None
    for tid in ids:
        r = dec.step(tid)
        if r.text:
            out.append(r.text)
        if r.stop_trigger:
            trigger = r.stop_trigger
            break
    assert trigger is StopTrigger.STOP_SEQUENCE
    text = "".join(out)
    assert "lazy" not in text and "dog" not in text
    assert text.startswith("the quick")


def test_decoder_partial_stop_prefix_jailed(mdc):
    tk = mdc.tokenizer()
    # stop seq never completes: its prefix must be held (jailed), not leaked
    dec = Decoder(tk, stop_sequences=["lazyXX"])
    ids = tk.encode("quick lazy").ids
    out = [r.text for r in map(dec.step, ids) if r.text]
    # 'lazy' could still become 'lazyXX' so it stays jailed at stream end
    assert "".join(out).startswith("quick")
    assert "lazy" not in "".join(out)


def test_decoder_max_tokens(mdc):
    tk = mdc.tokenizer()
    dec = Decoder(tk, max_tokens=3)
    ids = tk.encode("the quick brown fox jumps").ids
    triggers = [dec.step(t).stop_trigger for t in ids[:3]]
    assert triggers[-1] is StopTrigger.MAX_TOKENS


# ----------------------------------------------------- backend as operator


@pytest.mark.asyncio
async def test_full_pipeline_preproc_backend_engine(mdc):
    pre = OpenAIPreprocessor(mdc)
    tk = mdc.tokenizer()
    reply_ids = tk.encode("the quick brown fox").ids
    eos = mdc.model_info.eos_token_ids[0]
    outputs = [Annotated.from_data(BackendOutput(token_ids=[t]))
               for t in reply_ids]
    outputs.append(Annotated.from_data(BackendOutput(token_ids=[eos])))
    engine = RecordingEngine(outputs)
    pipeline = link(pre, Backend(mdc), engine)

    req = {"model": "tiny",
           "messages": [{"role": "user", "content": "say something"}]}
    stream = await pipeline.generate(Context(req))
    chunks = [a.data async for a in stream if a.data is not None]
    text = "".join(c["choices"][0]["delta"].get("content", "")
                   for c in chunks if c["choices"])
    assert text == "the quick brown fox"
    finals = [c["choices"][0]["finish_reason"] for c in chunks if c["choices"]]
    assert finals[-1] == "stop"
    # engine saw a PreprocessedRequest
    seen = engine.requests[0].data
    assert seen.token_ids and seen.eos_token_ids == [eos]


@pytest.mark.asyncio
async def test_pipeline_stop_sequence_stops_engine(mdc):
    pre = OpenAIPreprocessor(mdc)
    tk = mdc.tokenizer()
    reply_ids = tk.encode("hello world STOP more text").ids
    outputs = [Annotated.from_data(BackendOutput(token_ids=[t]))
               for t in reply_ids]
    engine = RecordingEngine(outputs)
    pipeline = link(pre, Backend(mdc), engine)
    req = {"model": "tiny", "stop": ["STOP"],
           "messages": [{"role": "user", "content": "go"}]}
    ctx = Context(req)
    stream = await pipeline.generate(ctx)
    chunks = [a.data async for a in stream if a.data is not None]
    text = "".join(c["choices"][0]["delta"].get("content", "")
                   for c in chunks if c["choices"])
    assert "STOP" not in text and "more" not in text
    assert ctx.ctx.is_stopped  # backend told the engine to halt
    finals = [c["choices"][0]["finish_reason"] for c in chunks if c["choices"]]
    assert finals[-1] == "stop"


@pytest.mark.asyncio
async def test_token_ids_annotation(mdc):
    pre = OpenAIPreprocessor(mdc)
    engine = RecordingEngine(
        [Annotated.from_data(BackendOutput(
            token_ids=[1], finish_reason=FinishReason.EOS))])
    pipeline = link(pre, Backend(mdc), engine)
    req = {"model": "tiny",
           "messages": [{"role": "user", "content": "hi"}],
           "nvext": {"annotations": ["token_ids"]}}
    stream = await pipeline.generate(Context(req))
    events = [a async for a in stream]
    assert any(a.event == "token_ids" for a in events)


def test_sentencepiece_routing(tmp_path):
    """.model files route to the sentencepiece kind, which LOADS in every
    image since round 4 (native engine llm/sp_model.py when the
    `sentencepiece` package is absent — reference tokenizers/sp.rs is
    the second tokenizer kind; full coverage in test_sp_tokenizer.py).
    A corrupt .model still fails with a clear error, not an import
    crash."""
    from dynamo_tpu.llm.tokenizer import (SentencePieceTokenizer,
                                          load_tokenizer)
    fake = tmp_path / "tokenizer.model"
    fake.write_bytes(b"\x00spm")
    with pytest.raises(Exception):       # invalid model file, either impl
        load_tokenizer(str(fake))
    real = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "data", "sp", "tiny.model")
    tk = load_tokenizer(real)
    assert isinstance(tk, SentencePieceTokenizer)
    assert tk.decode(tk.encode("the dog").ids) == "the dog"


def test_dir_prefers_hf_tokenizer_json(tmp_path):
    from dynamo_tpu.llm.tokenizer import (HuggingFaceTokenizer,
                                          load_tokenizer)
    # a dir with both artifacts prefers tokenizer.json (HF kind)
    from tests.fixtures import build_tiny_model_dir
    d = tmp_path / "both"
    build_tiny_model_dir(str(d))
    (d / "tokenizer.model").write_bytes(b"\x00spm")
    assert isinstance(load_tokenizer(str(d)), HuggingFaceTokenizer)


# ------------------------------------------------ the off-thread tokenize
#
# A long prompt's encode runs on a worker thread through the tokenizer's
# lock-releasing entry (OpenAIPreprocessor._tokenize); the ids are the
# inline branch's, the event loop goes on meanwhile, and a tokenizer kind
# that declares no such entry stays inline.

import asyncio  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

from dynamo_tpu.llm import preprocessor as preprocessor_mod  # noqa: E402
from dynamo_tpu.runtime.tracing import Trace, use_trace  # noqa: E402

_SHORT_TEXT = "the quick brown fox señor açaí 日本語"
_LONG_TEXT = "over the lazy dog, hello world and more text. " * 400


def _words(n: int) -> str:
    """n words of 16 characters: 1,000 pass the threshold inside the tiny
    model's context."""
    return "abcdefghijklmnop " * n


def _tokenized() -> dict:
    """The counter's value by branch."""
    return {s.labels["branch"]: s.value
            for m in preprocessor_mod.TOKENIZED_PROMPT_TOKENS.collect()
            for s in m.samples if s.name.endswith("_total")}


def _no_pool():
    raise AssertionError("the inline branch entered the executor")


async def _generate(pre, req) -> tuple:
    """One request through ``generate``: (the PreprocessedRequest the
    engine saw, the request's ``tokenize`` span)."""
    engine = RecordingEngine([])
    trace = Trace("r1")
    with use_trace(trace, finish=False):
        await pre.generate(Context(req), engine)
    (tok,) = [s for s in trace.spans if s.name == "tokenize"]
    (outer,) = [s for s in trace.spans if s.name == "preprocess"]
    assert outer.start <= tok.start and tok.end <= outer.end
    return engine.requests[0].data, tok


@pytest.mark.parametrize("special", [False, True])
@pytest.mark.parametrize("text", [_SHORT_TEXT, _LONG_TEXT],
                         ids=["short", "long"])
def test_hf_unlocked_entry_gives_the_same_ids(mdc, text, special):
    tk = mdc.tokenizer()
    assert len(_LONG_TEXT) >= preprocessor_mod.OFFTHREAD_MIN_CHARS
    want = tk.encode(text, add_special_tokens=special).ids
    assert want
    assert tk.encode_ids(text, add_special_tokens=special) == want
    assert tk.encode_ids_unlocked(text, add_special_tokens=special) == want


@pytest.mark.asyncio
@pytest.mark.parametrize("kind", ["chat", "completion"])
async def test_generate_ids_equal_from_both_branches(mdc, monkeypatch, kind):
    pre = OpenAIPreprocessor(mdc)
    text = "hello world, say something about the quick brown fox " * 3
    if kind == "chat":
        req = {"model": "tiny", "messages": [{"role": "user", "content": text}]}
        want = pre.preprocess_chat(ChatCompletionRequest(**req)).token_ids
    else:
        req = {"model": "tiny", "prompt": text}
        want = pre.preprocess_completion(CompletionRequest(**req)).token_ids
    before = _tokenized()
    # under the threshold: inline, and no executor is entered
    with monkeypatch.context() as m:
        m.setattr(preprocessor_mod, "_pool", _no_pool)
        seen, span = await _generate(pre, req)
    assert seen.token_ids == want
    assert span.attrs == {"offthread": False, "tokens": len(want)}
    # the same request with the threshold below its length: the worker's ids
    monkeypatch.setattr(preprocessor_mod, "OFFTHREAD_MIN_CHARS", 64)
    seen, span = await _generate(pre, req)
    assert seen.token_ids == want
    assert span.attrs == {"offthread": True, "tokens": len(want)}
    after = _tokenized()
    assert {k: after[k] - before[k] for k in after} == {
        "inline": len(want), "offthread": len(want)}


class _SleepyTokenizer:
    """A tokenizer whose lock-releasing entry takes 200 ms of wall time
    (``time.sleep`` releases the interpreter lock as the real one does)."""

    def __init__(self, fail=None):
        self.fail = fail
        self.threads = []

    def _ids(self, text):
        self.threads.append(threading.current_thread().name)
        if self.fail is not None:
            raise self.fail
        return [1 + (len(w) % 7) for w in text.split()]

    def encode_ids(self, text, add_special_tokens=False):
        return self._ids(text)

    def encode_ids_unlocked(self, text, add_special_tokens=False):
        time.sleep(0.2)
        return self._ids(text)


class _InlineOnlyTokenizer(_SleepyTokenizer):
    """A kind that does not declare the entry."""
    encode_ids_unlocked = None


async def _heartbeat(ticks: list) -> None:
    while True:
        ticks.append(time.monotonic())
        await asyncio.sleep(0.01)


@pytest.mark.asyncio
async def test_the_loop_runs_while_a_long_prompt_is_encoded(mdc):
    pre = OpenAIPreprocessor(mdc)
    pre.tokenizer = tk = _SleepyTokenizer()
    ticks: list = []
    beat = asyncio.ensure_future(_heartbeat(ticks))
    try:
        await asyncio.sleep(0)
        n0 = len(ticks)
        seen, span = await _generate(
            pre, {"model": "tiny", "prompt": _words(1000)})
        during = len(ticks) - n0
        # under the threshold the same tokenizer is called on this thread
        short, short_span = await _generate(
            pre, {"model": "tiny", "prompt": _words(30)})
    finally:
        beat.cancel()
    assert during >= 10, f"the loop ticked {during} times in 200 ms"
    assert len(seen.token_ids) == 1000 and len(short.token_ids) == 30
    assert span.attrs["offthread"] and not short_span.attrs["offthread"]
    assert tk.threads[0].startswith("tokenize")
    assert tk.threads[1] == threading.current_thread().name


@pytest.mark.asyncio
@pytest.mark.parametrize("kind", ["sentencepiece", "undeclared", "none"])
async def test_a_kind_without_the_entry_stays_inline(mdc, monkeypatch, kind):
    pre = OpenAIPreprocessor(mdc)
    if kind == "sentencepiece":
        from dynamo_tpu.llm.tokenizer import load_tokenizer
        pre.tokenizer = load_tokenizer(os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "data", "sp", "tiny.model"))
        text = "the dog " * 200
    elif kind == "undeclared":
        class Plain:
            def encode_ids(self, text, add_special_tokens=False):
                return [1] * len(text.split())
        pre.tokenizer = Plain()
        text = "word " * 200
    else:
        pre.tokenizer = _InlineOnlyTokenizer()
        text = "word " * 200
    monkeypatch.setattr(preprocessor_mod, "OFFTHREAD_MIN_CHARS", 64)
    monkeypatch.setattr(preprocessor_mod, "_pool", _no_pool)
    seen, span = await _generate(pre, {"model": "tiny", "prompt": text})
    assert span.attrs == {"offthread": False, "tokens": len(seen.token_ids)}
    assert seen.token_ids == pre.tokenizer.encode_ids(text)


@pytest.mark.asyncio
@pytest.mark.parametrize("words", [30, 1000], ids=["inline", "offthread"])
async def test_a_tokenizer_error_reaches_the_handler(mdc, words):
    pre = OpenAIPreprocessor(mdc)
    pre.tokenizer = _SleepyTokenizer(fail=ValueError("bad text"))
    engine = RecordingEngine([])
    with pytest.raises(ValueError, match="bad text"):
        await pre.generate(
            Context({"model": "tiny", "prompt": _words(words)}), engine)
    assert not engine.requests       # nothing was dispatched


@pytest.mark.asyncio
@pytest.mark.parametrize("words", [30, 1000], ids=["inline", "offthread"])
async def test_a_context_overflow_is_the_same_error(mdc, monkeypatch, words):
    pre = OpenAIPreprocessor(mdc)
    pre.tokenizer = _SleepyTokenizer()
    monkeypatch.setattr(mdc.model_info, "context_length", 16)
    with pytest.raises(ValueError, match="exceeds model context"):
        await pre.generate(
            Context({"model": "tiny", "prompt": _words(words)}),
            RecordingEngine([]))


@pytest.mark.asyncio
async def test_a_cancelled_request_drops_the_workers_result(mdc):
    pre = OpenAIPreprocessor(mdc)
    pre.tokenizer = tk = _SleepyTokenizer()
    engine = RecordingEngine([])
    before = _tokenized()
    task = asyncio.ensure_future(pre.generate(
        Context({"model": "tiny", "prompt": _words(1000)}), engine))
    await asyncio.sleep(0.05)        # the worker is inside the encode
    task.cancel()
    with pytest.raises(asyncio.CancelledError):
        await task
    await asyncio.sleep(0.3)         # the worker finishes; nobody listens
    assert tk.threads and not engine.requests
    assert _tokenized() == before


def test_encode_still_returns_the_token_strings(mdc):
    tk = mdc.tokenizer()
    enc = tk.encode("hello world")
    assert enc.tokens and len(enc.tokens) == len(enc.ids)
    assert [tk.token_to_id(t) for t in enc.tokens] == enc.ids
    assert tk.encode_ids("hello world") == enc.ids


def test_the_front_end_exports_prompt_tokens_by_branch():
    from dynamo_tpu.llm.http.metrics import ServiceMetrics
    text = ServiceMetrics().render().decode()
    for branch, tokens in _tokenized().items():
        assert (f'nv_llm_http_service_tokenized_prompt_tokens_total'
                f'{{branch="{branch}"}} {tokens}') in text
    assert set(_tokenized()) == {"inline", "offthread"}
