"""Median ``ingest_ms`` of the ``first_token`` flight records: from the
request's first byte to its enqueue at the engine — the HTTP parser and the
event-loop hops to the handler (``http.wire``), the body's read and JSON
parse, validation, pydantic, the template and the tokenizer. Wall time to
the enqueue: all of it is on the thread the engine loop shares but the
encode of a long prompt (12,288 characters or more), which runs on a worker
thread (``llm/preprocessor.py`` ``_tokenize``) and in the long-context cells
is most of the reading. A program without the record has nothing to
read."""

import statistics


def read(ctx):
    ms = [r["ingest_ms"] for r in ctx["flight"]
          if r["kind"] == "first_token" and "ingest_ms" in r]
    return statistics.median(ms) if ms else None
