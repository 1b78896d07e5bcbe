"""Native (C++) component loader: builds csrc/ into shared libs on first use
and memoizes. A failed build raises: the pure-Python counterparts are
selected by name (``prefer_native=False``, ``DYN_NATIVE_*=0``), never as a
silent fallback."""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

logger = logging.getLogger("dynamo_tpu.native")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_CSRC = os.path.join(_REPO_ROOT, "csrc")
_BUILD_DIR = os.path.join(_CSRC, "build")
_LOCK = threading.Lock()
_CACHE: dict = {}


class NativeLibError(RuntimeError):
    """The C++ toolchain is missing or a csrc/ build failed."""


# sanitizer build mode (csrc differential-fuzz hardening): the env knob
# DYN_NATIVE_SANITIZE selects instrumented builds — "asan", "ubsan", or
# "asan,ubsan". Sanitized objects land next to the normal ones under a
# distinct name (lib<name>.asan.<digest>.so) so the two build flavors
# never clobber each other. NOTE: dlopen'ing an ASan build
# into a non-ASan python requires LD_PRELOAD of libasan — the sanitized
# smoke test (tests/test_native_sanitize.py) runs its fuzz round in a
# subprocess with the preload set; in-process load() of an asan build
# without the preload raises NativeLibError.
_SAN_FLAGS = {
    "asan": ["-fsanitize=address", "-fno-omit-frame-pointer", "-g", "-O1"],
    "ubsan": ["-fsanitize=undefined", "-fno-sanitize-recover=undefined",
              "-g", "-O1"],
}


def sanitize_mode() -> Optional[str]:
    """Normalized DYN_NATIVE_SANITIZE value ("asan", "ubsan",
    "asan,ubsan") or None. Unknown tokens are rejected loudly — a typo'd
    knob silently building uninstrumented would defeat the fuzz ride."""
    raw = os.environ.get("DYN_NATIVE_SANITIZE", "").strip()
    if not raw or raw == "0":
        return None
    modes = sorted({m.strip() for m in raw.split(",") if m.strip()})
    for m in modes:
        if m not in _SAN_FLAGS:
            raise ValueError(
                f"DYN_NATIVE_SANITIZE={raw!r}: unknown sanitizer {m!r} "
                f"(supported: {sorted(_SAN_FLAGS)})")
    return ",".join(modes)


def _digest(srcs: list, flags: list) -> str:
    """Content digest of everything that decides the binary: the source
    files' bytes and the full flag list."""
    h = hashlib.sha256()
    for path in srcs:
        h.update(os.path.basename(path).encode() + b"\0")
        with open(path, "rb") as f:
            h.update(f.read())
        h.update(b"\0")
    h.update("\0".join(flags).encode())
    return h.hexdigest()[:16]


def _build(name: str, sources: list, extra_flags: Optional[list] = None,
           sanitize: Optional[str] = None) -> str:
    """Path of lib<name>[.<sanitize>].<digest>.so under csrc/build/,
    compiled first if no file of that name exists. The digest covers the
    sources (csrc/ files git tracks) and the flags, so a library built
    from another tree's sources — csrc/build/ is git-ignored and travels
    with a copied directory — never matches and is never loaded."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    tag = "" if not sanitize else "." + sanitize.replace(",", "-")
    srcs = [os.path.join(_CSRC, s) for s in sources]
    san_flags = [f for m in (sanitize.split(",") if sanitize else [])
                 for f in _SAN_FLAGS[m]]
    flags = ["-O3", "-std=c++17", "-shared", "-fPIC",
             *san_flags, *(extra_flags or [])]
    out = os.path.join(_BUILD_DIR,
                       f"lib{name}{tag}.{_digest(srcs, flags)}.so")
    if os.path.exists(out):
        return out
    # build beside the target and rename: a concurrent builder (xdist
    # workers, sibling serve workers) never dlopens a half-written file
    tmp = f"{out}.{os.getpid()}.tmp"
    cmd = ["g++", *flags, "-o", tmp, *srcs]
    logger.info("building native lib: %s", " ".join(cmd))
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, out)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return out


def build(name: str, sources: list,
          extra_flags: Optional[list] = None,
          sanitize: Optional[str] = None) -> Optional[str]:
    """Build without dlopen'ing (the sanitized-fuzz harness builds in the
    parent and loads in an LD_PRELOADed subprocess). Returns the .so path
    or None when the toolchain is missing/fails — the harness skips on
    None; nothing on the serving path calls this."""
    try:
        return _build(name, sources, extra_flags, sanitize=sanitize)
    except (subprocess.CalledProcessError, OSError) as e:
        detail = getattr(e, "stderr", "") or str(e)
        logger.warning("native build %s failed (%s)", name,
                       detail.strip()[:500])
        return None


def load(name: str, sources: list,
         extra_flags: Optional[list] = None) -> ctypes.CDLL:
    """Build (unless a library with this source+flag digest exists) and
    dlopen csrc/<sources> as lib<name>.<digest>.so — instrumented per
    DYN_NATIVE_SANITIZE when set. Raises NativeLibError when the
    toolchain or the build fails."""
    sanitize = sanitize_mode()
    key = (name, sanitize)
    with _LOCK:
        if key in _CACHE:
            return _CACHE[key]
        try:
            path = _build(name, sources, extra_flags, sanitize=sanitize)
            lib = ctypes.CDLL(path)
        except (subprocess.CalledProcessError, OSError) as e:
            detail = getattr(e, "stderr", "") or str(e)
            raise NativeLibError(
                f"native lib {name} unavailable: "
                f"{detail.strip()[:500]}") from e
        _CACHE[key] = lib
        return lib
