"""Process-level JAX set-up shared by every entry point that compiles
for the chip: where compiled programs are cached, and which devices the
process serves from."""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — a fixed path, because the path is part of the
# cache key's directory and a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# What this process's compiles cost, fed by JAX's own monitoring events
# once :func:`enable_compile_cache` has run (the BLOCK_EVENTS idiom).
COMPILE_STATS = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}


def _watch_compiles() -> None:
    from jax import monitoring

    def on_event(name: str, **_: object) -> None:
        if name == "/jax/compilation_cache/cache_hits":
            COMPILE_STATS["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            COMPILE_STATS["cache_misses"] += 1

    def on_duration(name: str, secs: float, **_: object) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILE_STATS["compile_s"] += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
    no other directory is set here; otherwise the cache lives in the
    checkout.  Called from entry points only — importing the package
    (and so the test suite) never turns the cache on.
    """
    _watch_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update(
        "jax_compilation_cache_dir", str(DEFAULT_COMPILE_CACHE_DIR)
    )
    return str(DEFAULT_COMPILE_CACHE_DIR)


def runtime_report() -> dict:
    """Device, compile cost, kernel paths taken and peak device memory of
    this process — what ``/health`` and ``chip_smoke.py`` print."""
    import jax

    from generativeaiexamples_tpu.ops.dispatch import TAKEN

    stats = jax.local_devices()[0].memory_stats() or {}
    return {
        "device": device_report(),
        "compile": {
            **COMPILE_STATS,
            "compile_s": round(COMPILE_STATS["compile_s"], 1),
            "cache_dir": jax.config.jax_compilation_cache_dir,
        },
        "kernel_paths": dict(sorted(TAKEN.items())),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
    }


def device_report() -> dict:
    """The device as JAX reports it — carried by every result a chip
    run prints, so a number can never be read without its device."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def require_accelerator(what: str) -> dict:
    """Refuse to run ``what`` on the CPU unless the CPU was asked for.

    A process meant for the CPU says so with ``JAX_PLATFORMS=cpu`` in
    its environment (tests, the chain server next to an engine); one
    that finds no TPU without having said so exits instead of serving
    an 8B model from the host.  Returns :func:`device_report`.
    """
    report = device_report()
    asked = os.environ.get("JAX_PLATFORMS", "").lower().split(",")
    if report["platform"] == "cpu" and "cpu" not in asked:
        raise SystemExit(
            f"{what}: no TPU found (JAX's backend is "
            f"{report['platform']!r}); set JAX_PLATFORMS=cpu to run on "
            "the CPU on purpose"
        )
    return report
