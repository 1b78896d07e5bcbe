"""The system under test, inside the benchmark's process.

Copied from ``chip_smoke.py`` (PR 25) so that the yardstick does not move
when the smoke does: ``require_tpu``, ``CacheWatch``, ``Server`` (the
launcher's own objects — ``launch.run.build_engine`` + ``run_http`` — over a
real socket) and ``write_model_dir``. What differs from the original:

* the model directory is written from a ``benchmark/configs/<name>.json``
  file, and its tokenizer is one the benchmark builds itself: one token per
  id, each decoding to a text of its own (``<t123>``), so that a prompt has
  exactly the length the traffic file asks for, every streamed token yields a
  chunk, and a served text maps back to its ids with no ambiguity;
* the weights' seed is the run's ``--seed`` (the launcher has no flag for
  ``EngineConfig.seed``, so the one call that builds it is wrapped);
* nothing re-lowers the served programs (that is the smoke's business).
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import socket
import time

MODEL_NAME = "bench"


def note(key: str, value) -> None:
    print(f"# {key}: {value}", flush=True)


def require_tpu(chips: int):
    """The platform check, before anything else touches the repo: no TPU,
    or fewer chips than the cell asks for, and nothing is run."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found platform {devices[0].platform!r}, not a "
            "TPU; nothing was run")
    if len(devices) < chips:
        raise SystemExit(
            f"benchmark: the cell asks for {chips} chip(s), JAX found "
            f"{len(devices)}; nothing was run")
    return devices


class CacheWatch:
    """Cold or warm, as JAX itself reports it: persistent-cache hits and
    misses of this process, and how many back-end compilations ran (a
    cache hit is not one)."""

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

    @property
    def programs(self) -> int:
        """Programs built so far, from the cache or by the compiler: what
        must not grow inside a measured window."""
        return self.hits + self.misses

    def report(self, tag: str) -> None:
        state = ("cold" if not self.hits else
                 "warm" if not self.misses else "partly warm")
        note(f"compile_cache[{tag}]",
             f"dir={self.dir} state={state} "
             f"entries_at_start={self.entries_at_start} "
             f"hits={self.hits} misses={self.misses}")


def token_text(ids) -> str:
    """The text whose tokenization is exactly ``ids``."""
    return "".join(f"<t{i}>" for i in ids)


def text_tokens(text: str) -> list:
    """Inverse of the tokenizer's decode: ``<t5> <t7>`` → [5, 7]."""
    import re
    ids = re.findall(r"<t(\d+)>", text)
    rest = re.sub(r"<t\d+>|\s", "", text)
    if rest:
        raise ValueError(f"served text holds more than tokens: {rest[:40]!r}")
    return [int(i) for i in ids]


def write_model_dir(path: str, hf_config: dict) -> str:
    """config.json as published + the one-token-per-id tokenizer."""
    from tokenizers import Tokenizer, models
    os.makedirs(path, exist_ok=True)
    vocab = int(hf_config["vocab_size"])
    tok = Tokenizer(models.WordLevel({"<t0>": 0}, unk_token="<t0>"))
    tok.add_tokens([f"<t{i}>" for i in range(1, vocab)])
    if tok.get_vocab_size() != vocab:
        raise AssertionError("tokenizer not padded to the model vocabulary")
    tok.save(os.path.join(path, "tokenizer.json"))
    # every request sets ignore_eos; the id only has to exist
    cfg = dict(hf_config, eos_token_id=vocab - 1, bos_token_id=None)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(path, "tokenizer_config.json"), "w") as f:
        json.dump({"eos_token": f"<t{vocab - 1}>"}, f)
    return path


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Server:
    """The launcher's own objects, inside this process."""

    def __init__(self, model_dir: str, flags: list, seed: int):
        self.model_dir = model_dir
        self.flags = flags
        self.seed = seed
        self.port = free_port()

    async def __aenter__(self):
        from dynamo_tpu.launch import run
        self.args = run.build_parser().parse_args(
            ["in=http", "out=jax", "--model-path", self.model_dir,
             "--model-name", MODEL_NAME, "--random-weights",
             "--http-host", "127.0.0.1", "--http-port", str(self.port),
             *self.flags])
        src, out = run.parse_io(self.args.io)
        if (src, out) != ("http", "jax"):
            raise AssertionError("in=http out=jax")
        # weights from --seed: EngineConfig.seed has no launcher flag
        launcher_config = run.engine_config
        seed = self.seed
        run.engine_config = lambda args: dataclasses.replace(
            launcher_config(args), seed=seed)
        t0 = time.monotonic()
        try:
            self.runtime = await run.make_runtime(self.args)
            self.engine, self.mdc, self.core = await run.build_engine(
                self.args, out, self.runtime)
        finally:
            run.engine_config = launcher_config
        self.build_s = time.monotonic() - t0
        pipeline = run.link_pipeline(self.engine, self.mdc)
        self.http_task = asyncio.create_task(
            run.run_http(self.args, pipeline, self.core))
        import aiohttp
        self.session = aiohttp.ClientSession(
            timeout=aiohttp.ClientTimeout(total=900))
        self.base = f"http://127.0.0.1:{self.port}"
        for _ in range(400):
            if self.http_task.done():
                self.http_task.result()       # surfaces the bind error
            try:
                async with self.session.get(self.base + "/health") as r:
                    if r.status == 200:
                        break
            except aiohttp.ClientConnectionError:
                await asyncio.sleep(0.025)
        else:
            raise AssertionError("HTTP service never answered /health")
        return self

    async def __aexit__(self, *exc):
        await self.session.close()
        self.http_task.cancel()
        await asyncio.gather(self.http_task, return_exceptions=True)
        await self.core.stop()
        await self.runtime.shutdown()

    async def complete(self, ids: list, max_tokens: int) -> dict:
        """One greedy completion over HTTP, not streamed, with the chosen
        tokens' logprobs → {"ids": [...], "logprobs": [...]}."""
        body = {"model": MODEL_NAME, "prompt": token_text(ids),
                "max_tokens": max_tokens, "temperature": 0, "logprobs": 1,
                "nvext": {"ignore_eos": True}}
        async with self.session.post(self.base + "/v1/completions",
                                     json=body) as r:
            text = await r.text()
            if r.status != 200:
                raise AssertionError(
                    f"POST /v1/completions → {r.status}: {text[:300]}")
        resp = json.loads(text)
        choice = resp["choices"][0]
        out = {"ids": text_tokens(choice["text"]),
               "logprobs": list(choice["logprobs"]["token_logprobs"]),
               "usage": resp.get("usage") or {}}
        if not (len(out["ids"]) == len(out["logprobs"]) == max_tokens
                == out["usage"].get("completion_tokens")):
            raise AssertionError(
                f"asked for {max_tokens} tokens, got {len(out['ids'])} ids, "
                f"{len(out['logprobs'])} logprobs, usage {out['usage']}")
        if out["usage"].get("prompt_tokens") != len(ids):
            raise AssertionError(
                f"prompt of {len(ids)} tokens counted as "
                f"{out['usage'].get('prompt_tokens')}")
        return out
