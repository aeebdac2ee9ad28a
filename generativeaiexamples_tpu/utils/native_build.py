"""Shared build-on-first-use loader for the in-tree C++ libraries.

One copy of the repo-root resolution, build key, g++ invocation, and
per-library lock/cache used by ``retrieval.native`` (vecsearch) and
``engine.native_tokenizer`` (wordpiece).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

from generativeaiexamples_tpu.core.logging import get_logger

logger = get_logger(__name__)

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_lock = threading.Lock()
_cache: dict[str, ctypes.CDLL] = {}


_FLAGS = ("-O3", "-march=native", "-shared", "-fPIC", "-std=c++17")


def _host_cpu() -> bytes:
    """What ``-march=native`` resolves against: the CPU's feature flags."""
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"flags", b"Features")):
                    return line
    except OSError:
        pass
    return os.uname().machine.encode()


def load_native_library(src_name: str) -> ctypes.CDLL:
    """Load ``native/<src_name>.cpp`` as
    ``native/build/lib<src_name>-<key>.so``, compiling it when no
    library with that key exists.

    The key hashes the source's content, the compiler flags and the
    host CPU's feature flags, so a library built from other source, or
    on another machine (``-march=native``) and carried along with the
    tree, is never the one loaded.
    """
    with _lock:
        if src_name in _cache:
            return _cache[src_name]
        src = os.path.join(_REPO_ROOT, "native", f"{src_name}.cpp")
        with open(src, "rb") as f:
            key = hashlib.sha256(
                f.read() + " ".join(_FLAGS).encode() + _host_cpu()
            ).hexdigest()[:16]
        lib_path = os.path.join(
            _REPO_ROOT, "native", "build", f"lib{src_name}-{key}.so"
        )
        if not os.path.exists(lib_path):
            os.makedirs(os.path.dirname(lib_path), exist_ok=True)
            # Build beside the target and rename: another process
            # (a test worker) must never load a half-written library.
            tmp = f"{lib_path}.{os.getpid()}.tmp"
            cmd = ["g++", *_FLAGS, "-o", tmp, src]
            logger.info("building native %s: %s", src_name, " ".join(cmd))
            subprocess.run(cmd, check=True, capture_output=True)
            os.replace(tmp, lib_path)
        lib = ctypes.CDLL(lib_path)
        _cache[src_name] = lib
        return lib
