"""Layered runtime configuration.

Reference: lib/runtime/src/config.rs:24-170 — figment layering: built-in
defaults → ``/opt/dynamo/defaults/runtime.toml`` → ``/opt/dynamo/etc/
runtime.toml`` → env ``DYN_RUNTIME_*`` / ``DYN_WORKER_*``, producing
``RuntimeConfig{num_worker_threads, max_blocking_threads}`` and
``WorkerConfig``. Python analog with the same precedence:

    defaults → DYN_RUNTIME_CONFIG_PATH toml (or /opt/dynamo_tpu/etc/
    runtime.toml when present) → DYN_RUNTIME_* / DYN_WORKER_* env

Field name mapping: env keys are upper-snake of the field, e.g.
``DYN_RUNTIME_LEASE_TTL=5`` or ``DYN_WORKER_GRACEFUL_SHUTDOWN_TIMEOUT=10``.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import tomllib
from typing import Any, Optional

logger = logging.getLogger("dynamo_tpu.runtime.config")

_DEFAULT_TOML_PATHS = ("/opt/dynamo_tpu/defaults/runtime.toml",
                       "/opt/dynamo_tpu/etc/runtime.toml")

__all__ = ["RuntimeConfig", "WorkerConfig", "load_runtime_config",
           "load_worker_config"]


@dataclasses.dataclass
class RuntimeConfig:
    """Process-wide runtime knobs (reference RuntimeConfig)."""

    lease_ttl: float = 10.0            # discovery lease TTL seconds
    tcp_host: str = "127.0.0.1"        # response-plane bind host
    native_dataplane: bool = True      # C++ sender when buildable
    native_kvpool: bool = True         # C++ reuse pool when buildable
    max_blocking_threads: int = 64     # asyncio default-executor cap


@dataclasses.dataclass
class WorkerConfig:
    """Worker main-wrapper knobs (reference WorkerConfig, worker.rs)."""

    graceful_shutdown_timeout: float = 30.0
    discovery_addr: str = ""
    advertise_host: Optional[str] = None


def _coerce(value: str, type_name: str) -> Any:
    """Env string → the field's declared type (annotations are strings
    under `from __future__ import annotations`)."""
    if type_name == "bool":
        return value.strip().lower() not in ("0", "false", "no", "")
    if type_name == "float":
        return float(value)
    if type_name == "int":
        return int(value)
    if type_name.startswith("Optional"):
        return value or None
    return value


def _layer(cls, section: str, env_prefix: str):
    """defaults → toml [section] → env ``{env_prefix}_FIELD``."""
    values: dict = {}
    # toml layer
    paths = [p for p in _DEFAULT_TOML_PATHS if os.path.exists(p)]
    explicit = os.environ.get("DYN_RUNTIME_CONFIG_PATH")
    if explicit:
        paths.append(explicit)
    for path in paths:
        try:
            with open(path, "rb") as f:
                data = tomllib.load(f)
        except (OSError, tomllib.TOMLDecodeError) as e:
            logger.warning("skipping config file %s: %s", path, e)
            continue
        values.update(data.get(section, {}))
    # env layer (highest precedence)
    fields = {f.name: f for f in dataclasses.fields(cls)}
    kwargs: dict = {}
    for name, f in fields.items():
        if name in values:
            kwargs[name] = values[name]
        env_key = f"{env_prefix}_{name.upper()}"
        if env_key in os.environ:
            kwargs[name] = _coerce(os.environ[env_key], str(f.type))
    unknown = set(values) - set(fields)
    if unknown:
        logger.warning("unknown %s config keys ignored: %s", section,
                       sorted(unknown))
    return cls(**kwargs)


def load_runtime_config() -> RuntimeConfig:
    return _layer(RuntimeConfig, "runtime", "DYN_RUNTIME")


def load_worker_config() -> WorkerConfig:
    cfg = _layer(WorkerConfig, "worker", "DYN_WORKER")
    # legacy/primary env names used elsewhere in the runtime keep working
    if "DYN_DISCOVERY_ADDR" in os.environ:
        cfg.discovery_addr = os.environ["DYN_DISCOVERY_ADDR"]
    if "DYN_ADVERTISE_HOST" in os.environ:
        cfg.advertise_host = os.environ["DYN_ADVERTISE_HOST"]
    return cfg
