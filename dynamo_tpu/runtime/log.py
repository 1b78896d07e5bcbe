"""Logging setup: level filters and JSONL output.

Reference: lib/runtime/src/logging.rs:54-170 — tracing-subscriber driven by
``DYN_LOG`` (a level or ``target=level`` comma list) with an optional
custom JSONL formatter under ``DYN_LOGGING_JSONL``. Python analog over the
stdlib logging tree:

    DYN_LOG="info"                      # root level
    DYN_LOG="info,dynamo_tpu.kv=debug"  # per-module overrides
    DYN_LOGGING_JSONL=1                 # one JSON object per line

``setup_logging()`` is called by the worker wrapper, the daemon, and every
module CLI; calling it twice is a no-op unless ``force=True``.
"""

from __future__ import annotations

import json
import logging
import os
import sys
import time
from typing import Optional

__all__ = ["setup_logging", "JsonlFormatter"]

_configured = False


class JsonlFormatter(logging.Formatter):
    """One JSON object per line: ts, level, target (logger name), message,
    plus exception text when present (reference custom JSONL formatter)."""

    def format(self, record: logging.LogRecord) -> str:
        out = {
            "ts": round(record.created, 6),
            "iso": time.strftime("%Y-%m-%dT%H:%M:%S",
                                 time.gmtime(record.created))
                   + f".{int(record.msecs):03d}Z",
            "level": record.levelname,
            "target": record.name,
            "message": record.getMessage(),
        }
        if record.exc_info and record.exc_info[0] is not None:
            out["exception"] = self.formatException(record.exc_info)
        return json.dumps(out, ensure_ascii=False)


_LEVEL_NAMES = logging.getLevelNamesMapping()


def _parse_dyn_log(spec: str) -> tuple:
    """"info,foo.bar=debug" → (root_level, {module: level})."""
    root = logging.INFO
    per_module = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" in part:
            mod, _, lvl = part.partition("=")
            per_module[mod.strip()] = _LEVEL_NAMES.get(
                lvl.strip().upper(), logging.INFO)
        else:
            root = _LEVEL_NAMES.get(part.upper(), logging.INFO)
    return root, per_module


def setup_logging(level: Optional[str] = None, force: bool = False) -> None:
    global _configured
    if _configured and not force:
        return
    _configured = True
    spec = level or os.environ.get("DYN_LOG", "info")
    root_level, per_module = _parse_dyn_log(spec)
    handler = logging.StreamHandler(sys.stderr)
    if os.environ.get("DYN_LOGGING_JSONL", "") not in ("", "0", "false"):
        handler.setFormatter(JsonlFormatter())
    else:
        handler.setFormatter(logging.Formatter(
            "%(asctime)s %(name)s %(levelname)s %(message)s"))
    root = logging.getLogger()
    if force:
        root.handlers.clear()
    root.addHandler(handler)
    root.setLevel(root_level)
    for mod, lvl in per_module.items():
        logging.getLogger(mod).setLevel(lvl)
