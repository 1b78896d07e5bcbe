"""The load generator: a process of its own, so that the clients do not
share the server's interpreter lock. It imports no JAX and never touches
the chip.

Protocol, over its standard input and output (one JSON object per line):

    ← {"base": "http://127.0.0.1:PORT", "model": ..., "mix": {...},
       "seed": n, "seconds": s, "vocab": v, "rate_rps": r|null}
    → {"ready": true}
    ← {"go": <epoch seconds at which the load starts>}
    → {"result": {...}}          # after the window and its grace

The window opens ``ramp_s`` after the load starts and lasts ``seconds``.
Requests are timed from when they were due (open loop) or sent (closed
loop) to their first streamed token; gaps between a request's streamed
tokens are pooled. Only what falls inside the window is counted. Requests
still streaming at the end are dropped by closing their connections, which
the server sees as clients going away; they are neither failures nor
latency samples unless they started inside the window.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import aiohttp  # noqa: E402  (before "ready": its import takes 0.3 s)
import traffic  # noqa: E402
from server import token_text  # noqa: E402  (stdlib imports only)


class Run:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.mix = cfg["mix"]
        self.seconds = float(cfg["seconds"])
        self.ramp = float(self.mix.get("ramp_s", 0))
        self.grace = float(self.mix.get("grace_s", 10))
        self.requests = traffic.schedule(
            self.mix, cfg["seed"], self.seconds, cfg["vocab"],
            rate_rps=cfg.get("rate_rps"))
        self.records = []
        self.next = 0
        self.lateness = []

    # times below are seconds after the load started (t0 on the epoch clock)
    def now(self) -> float:
        return time.time() - self.t0

    @property
    def open_s(self) -> float:
        return self.ramp

    @property
    def close_s(self) -> float:
        return self.ramp + self.seconds

    async def one(self, session, req: dict, start: float) -> None:
        """One streamed completion; ``start`` is when its clock began."""
        rec = {"start": start, "first": None, "arrivals": [], "status": None,
               "asked": req["max_tokens"], "completion_tokens": None,
               "done": False, "error": None}
        self.records.append(rec)
        body = {"model": self.cfg["model"], "prompt": token_text(req["prompt"]),
                "max_tokens": req["max_tokens"], "temperature": 0,
                "stream": True, "stream_options": {"include_usage": True},
                "nvext": {"ignore_eos": True}}
        try:
            async with session.post(self.cfg["base"] + "/v1/completions",
                                    json=body) as r:
                rec["status"] = r.status
                if r.status != 200:
                    rec["error"] = (await r.text())[:200]
                    return
                async for raw in r.content:
                    if not raw.startswith(b"data:"):
                        continue
                    data = raw[5:].strip()
                    if data == b"[DONE]":
                        rec["done"] = True
                        break
                    t = self.now()
                    chunk = json.loads(data)
                    usage = chunk.get("usage")
                    if usage:
                        rec["completion_tokens"] = usage.get(
                            "completion_tokens")
                    choices = chunk.get("choices") or []
                    if choices and choices[0].get("text"):
                        if rec["first"] is None:
                            rec["first"] = t
                        rec["arrivals"].append(t)
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — a failed request is counted
            rec["error"] = f"{type(e).__name__}: {e}"[:200]

    async def open_loop(self, session) -> None:
        tasks = []
        for req in self.requests:
            wait = req["due"] - self.now()
            if wait > 0:
                await asyncio.sleep(wait)
            self.lateness.append(self.now() - req["due"])
            tasks.append(asyncio.create_task(
                self.one(session, req, req["due"])))
        self.tasks = tasks

    async def caller(self, session) -> None:
        while self.now() < self.close_s and self.next < len(self.requests):
            req = self.requests[self.next]
            self.next += 1
            await self.one(session, req, self.now())

    async def closed_loop(self, session) -> None:
        self.tasks = [asyncio.create_task(self.caller(session))
                      for _ in range(int(self.mix["clients"]))]

    async def run(self, t0: float) -> dict:
        self.t0 = t0
        self.tasks = []
        conn = aiohttp.TCPConnector(limit=0)
        timeout = aiohttp.ClientTimeout(total=None, sock_read=120)
        async with aiohttp.ClientSession(connector=conn,
                                         timeout=timeout) as session:
            await asyncio.sleep(max(0.0, t0 - time.time()))
            if self.mix["loop"] == "open":
                await self.open_loop(session)
            else:
                await self.closed_loop(session)
            await asyncio.sleep(max(0.0, self.close_s - self.now()))
            # the window is over: wait, at most grace_s, for the first
            # token of every request that started inside it
            deadline = self.close_s + self.grace
            while self.now() < deadline and any(
                    r["first"] is None and r["status"] in (None, 200)
                    and r["error"] is None and not r["done"]
                    and self.open_s <= r["start"] < self.close_s
                    for r in self.records):
                await asyncio.sleep(0.02)
            drained = self.now()
            for t in self.tasks:
                t.cancel()
            await asyncio.gather(*self.tasks, return_exceptions=True)
        return self.reduce(drained)

    def reduce(self, drained: float) -> dict:
        lo, hi = self.open_s, self.close_s
        inside = [r for r in self.records if lo <= r["start"] < hi]
        ttft, failures = [], []
        for r in inside:
            bad = None
            if r["status"] != 200 and r["status"] is not None:
                bad = f"status {r['status']}: {r['error']}"
            elif r["error"]:
                bad = r["error"]
            elif r["first"] is None:
                bad = "no first token within the grace time"
            elif r["done"] and not (len(r["arrivals"]) == r["asked"]
                                    == r["completion_tokens"]):
                bad = (f"asked {r['asked']} tokens, streamed "
                       f"{len(r['arrivals'])}, usage "
                       f"{r['completion_tokens']}")
            if bad:
                failures.append(bad)
            else:
                ttft.append(1e3 * (r["first"] - r["start"]))
        itl, tokens = [], 0
        for r in self.records:
            arr = r["arrivals"]
            tokens += sum(1 for t in arr if lo <= t < hi)
            itl.extend(1e3 * (b - a) for a, b in zip(arr, arr[1:])
                       if lo <= b < hi)
        in_flight_at_close = sum(
            1 for r in self.records
            if r["start"] < hi and not (r["done"] and r["arrivals"]
                                        and r["arrivals"][-1] < hi)
            and r["error"] is None)
        return {
            "attempted": len(inside), "failed": len(failures),
            "failures": failures[:5],
            "completed_in_window": sum(
                1 for r in self.records if r["done"] and r["arrivals"]
                and lo <= r["arrivals"][-1] < hi),
            "offered_total": len(self.records),
            "in_flight_at_close": in_flight_at_close,
            "ttft_ms": ttft, "itl_ms": itl, "tokens_in_window": tokens,
            "lateness_max_ms": 1e3 * max(self.lateness, default=0.0),
            "drained_after_close_s": drained - hi,
        }


def main() -> int:
    cfg = json.loads(sys.stdin.readline())
    run = Run(cfg)
    print(json.dumps({"ready": True, "requests": len(run.requests)}),
          flush=True)
    go = json.loads(sys.stdin.readline())
    result = asyncio.run(run.run(float(go["go"])))
    print(json.dumps({"result": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
