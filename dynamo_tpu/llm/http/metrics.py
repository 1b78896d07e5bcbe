"""Prometheus metrics for the HTTP frontend.

Reference: lib/llm/src/http/service/metrics.rs:36-346 — the
`nv_llm_http_service_*` counter/gauge/histogram matrix and the RAII
`InflightGuard` that guarantees the inflight gauge decrements and the request
counter lands in exactly one of {success, error, cancelled} ("status" label)
no matter how the stream ends.
"""

from __future__ import annotations

import time
from typing import Optional

from prometheus_client import (CollectorRegistry, Counter, Gauge, Histogram,
                               generate_latest)

from ..preprocessor import TOKENIZED_PROMPT_TOKENS

PREFIX = "nv_llm_http_service"

REQUEST_STATUS_SUCCESS = "success"
REQUEST_STATUS_ERROR = "error"
REQUEST_STATUS_CANCELLED = "cancelled"


class ServiceMetrics:
    def __init__(self, registry: Optional[CollectorRegistry] = None):
        self.registry = registry or CollectorRegistry()
        self.requests_total = Counter(
            f"{PREFIX}_requests_total",
            "Total requests by model/endpoint/type/status",
            ["model", "endpoint", "request_type", "status"],
            registry=self.registry)
        self.inflight = Gauge(
            f"{PREFIX}_inflight_requests",
            "Currently inflight requests",
            ["model", "endpoint"],
            registry=self.registry)
        self.request_duration = Histogram(
            f"{PREFIX}_request_duration_seconds",
            "End-to-end request duration",
            ["model", "endpoint"],
            registry=self.registry,
            buckets=(0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0))
        self.time_to_first_token = Histogram(
            f"{PREFIX}_time_to_first_token_seconds",
            "TTFT per streaming request",
            ["model", "endpoint"],
            registry=self.registry,
            buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))
        self.output_tokens = Counter(
            f"{PREFIX}_output_tokens_total",
            "Output tokens (streamed chunks) per model",
            ["model", "endpoint"],
            registry=self.registry)
        self.inter_token_latency = Histogram(
            f"{PREFIX}_inter_token_latency_seconds",
            "Gap between consecutive streamed tokens (ITL)",
            ["model", "endpoint"],
            registry=self.registry,
            buckets=(0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5,
                     1.0, 2.5))

        # the SSE write collector (sse_flush.py): chunks per flush is the
        # number of streams whose tokens one loop iteration wrote together
        self.sse_flushes = Counter(
            f"{PREFIX}_sse_flushes_total",
            "Passes of the SSE write collector that wrote to a transport",
            registry=self.registry)
        self.sse_flushed_chunks = Counter(
            f"{PREFIX}_sse_flushed_chunks_total",
            "SSE chunks those passes wrote",
            registry=self.registry)
        # how often the preprocessor's off-thread encode engages: its
        # own process-wide counter, shown beside this service's series
        self.registry.register(TOKENIZED_PROMPT_TOKENS)

    def render(self) -> bytes:
        return generate_latest(self.registry)

    def inflight_guard(self, model: str, endpoint: str, streaming: bool,
                       start: Optional[float] = None) -> "InflightGuard":
        return InflightGuard(self, model, endpoint, streaming, start)


class InflightGuard:
    """RAII-style inflight/request-status guard (reference metrics.rs
    `InflightGuard`): create on request admission, call `mark_ok()` on clean
    completion; anything else counts as error/cancelled on close. ``start``
    (monotonic) is when the request's first byte arrived, where the caller
    knows it: the duration and time-to-first-token histograms count from
    there, so they hold what the client waited for."""

    def __init__(self, metrics: ServiceMetrics, model: str, endpoint: str,
                 streaming: bool, start: Optional[float] = None):
        self._m = metrics
        self.model = model
        self.endpoint = endpoint
        self.request_type = "stream" if streaming else "unary"
        self._status = REQUEST_STATUS_ERROR
        self._start = time.monotonic() if start is None else start
        self._first_token_at: Optional[float] = None
        self._last_token_at: float = 0.0
        self._m.inflight.labels(model, endpoint).inc()
        self._closed = False

    def mark_ok(self) -> None:
        self._status = REQUEST_STATUS_SUCCESS

    def mark_cancelled(self) -> None:
        self._status = REQUEST_STATUS_CANCELLED

    def note_token(self, n: int = 1) -> None:
        now = time.monotonic()
        if self._first_token_at is None:
            self._first_token_at = now
            self._m.time_to_first_token.labels(self.model, self.endpoint).observe(
                now - self._start)
        else:
            # token-weighted ITL: the arrival gap is split across the n
            # tokens this chunk carries and observed once per token, so
            # histogram _count tracks output_tokens and quantiles weight
            # per token. n comes from the chunk's text-bearing choices —
            # a single choice whose delta batches several tokens' text
            # still counts once (the HTTP layer can't see token counts).
            per_tok = (now - self._last_token_at) / max(n, 1)
            itl = self._m.inter_token_latency.labels(self.model,
                                                     self.endpoint)
            for _ in range(max(n, 1)):
                itl.observe(per_tok)
        self._last_token_at = now
        self._m.output_tokens.labels(self.model, self.endpoint).inc(n)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._m.inflight.labels(self.model, self.endpoint).dec()
        self._m.requests_total.labels(
            self.model, self.endpoint, self.request_type, self._status).inc()
        self._m.request_duration.labels(self.model, self.endpoint).observe(
            time.monotonic() - self._start)

    def __enter__(self) -> "InflightGuard":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()
