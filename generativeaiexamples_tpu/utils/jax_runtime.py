"""Process-level JAX set-up shared by every entry point that compiles
for the chip: where compiled programs are cached, which devices the
process serves from, and the record of every executable it asked for."""

from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from pathlib import Path
from typing import Callable, Iterator, Optional

# <checkout>/.jax_cache — a fixed path, because the path is part of the
# cache key's directory and a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

# JAX's monitoring events an executable's making emits, in order and on
# the thread that asked: the function's trace, its lowering, what the
# persistent cache said (compiler.py::compile_or_get_cached), and the
# backend's compile, which on a cache hit is the read.
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

ASKERS = ("build", "tick", "other")
SETUP_STAGES = ("params", "state", "programs")


class _Asker(threading.local):
    """Who asks for executables on this thread: ``build`` inside
    ``Scheduler.__init__``, ``tick`` on a scheduler's tick thread,
    ``other`` wherever nobody said.  ``facts`` is read when an executable
    closes (the tick's number, the running dispatch's program and shapes);
    ``sink`` is then handed the entry (the scheduler's own counters)."""

    who = "other"
    facts: Optional[Callable[[], dict]] = None
    sink: Optional[Callable[[dict], None]] = None
    # The set-up stage open on this thread: (name, since, annotation).
    stage: Optional[tuple] = None

    def __init__(self) -> None:  # once a thread
        # What JAX has said so far of the executable in the making.
        self.pending: dict = {}


class ExecutableRecord:
    """Every executable JAX made for this process or read from its
    persistent cache, one entry each, fed by JAX's own monitoring events
    once :func:`enable_compile_cache` has run; and the seconds of
    ``Scheduler.__init__`` by stage.

    An entry: ``fun_name``, ``trace_s`` (the Python function to a jaxpr,
    the jitted functions it calls included), ``lower_s``
    (jaxpr to StableHLO), ``backend_s`` (XLA's compile on a miss, the
    cache read on a hit), ``cache`` (``hit``, ``miss``: looked up and not
    found, or ``off``: no cache, or no key for this program),
    ``retrieval_s`` and ``saved_s`` (a hit's read, and the compile it
    spared, as the cache's entry says), ``t`` (``time.perf_counter()`` at
    its end: the clock of the tick record's ``t_start``), ``asked_by``
    and, from a tick thread, ``tick``, ``phase`` and the running
    dispatch's facts.  The newest ``limit`` entries are kept; the totals
    by ``asked_by`` are of all.  Nothing runs here unless JAX compiles.
    """

    def __init__(self, limit: int = 1024) -> None:
        self.lock = threading.Lock()
        self.entries: "collections.deque[dict]" = collections.deque(maxlen=limit)
        self.totals = {
            who: {
                "executables": 0, "hit": 0, "miss": 0, "off": 0,
                "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            }
            for who in ASKERS
        }
        self.setup = {"builds": 0, "build_s": 0.0}
        self.setup.update({f"{stage}_s": 0.0 for stage in SETUP_STAGES})
        self.asker = _Asker()

    # -- JAX's listeners ---------------------------------------------------

    def on_event(self, name: str, **_: object) -> None:
        if name == _CACHE_ASKED:
            import jax

            # JAX asks its cache wherever caching is not switched off,
            # with or without a directory to look in.
            if jax.config.jax_compilation_cache_dir:
                self.asker.pending["cache"] = "miss"  # until a hit
        elif name == _CACHE_HIT:
            self.asker.pending["cache"] = "hit"

    def on_duration(
        self, name: str, secs: float, fun_name: str = "", **_: object
    ) -> None:
        if name == _BACKEND:
            self._close(fun_name, secs)
        elif name == _TRACE:
            # By name: the jitted functions inside it are traced (and
            # timed) on the way, and a function whose jaxpr is cached
            # reports a second, empty trace.
            traces = self.asker.pending.setdefault("traces", {})
            traces[fun_name] = traces.get(fun_name, 0.0) + secs
        elif name == _LOWER:
            self.asker.pending["lower_s"] = secs
        elif name == _CACHE_READ:
            self.asker.pending["retrieval_s"] = secs
        elif name == _CACHE_SAVED:
            self.asker.pending["saved_s"] = secs

    def _close(self, fun_name: str, backend_s: float) -> None:
        asker = self.asker
        pending = asker.pending
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        entry = {
            "fun_name": fun_name,
            "trace_s": pending.get("traces", {}).get(fun_name, 0.0),
            "lower_s": pending.get("lower_s", 0.0),
            "backend_s": backend_s,
            "cache": pending.get("cache", "off"),
            "retrieval_s": pending.get("retrieval_s", 0.0),
            "saved_s": pending.get("saved_s", 0.0),
            "t": time.perf_counter(),
            "asked_by": asker.who,
        }
        pending.clear()
        if asker.facts is not None:
            entry.update(asker.facts())
        with self.lock:
            totals = self.totals[entry["asked_by"]]
            totals["executables"] += 1
            totals[entry["cache"]] += 1
            for stage in ("trace_s", "lower_s", "backend_s"):
                totals[stage] += entry[stage]
            self.entries.append(entry)
        if asker.sink is not None:
            asker.sink(entry)

    # -- who asks ----------------------------------------------------------

    @contextlib.contextmanager
    def asking(
        self,
        who: str,
        facts: Optional[Callable[[], dict]] = None,
        sink: Optional[Callable[[dict], None]] = None,
    ) -> Iterator[None]:
        """Executables this thread asks for inside the block read
        ``asked_by`` ``who``."""
        if who not in ASKERS:  # here, not inside JAX's compile
            raise ValueError(f"asked_by is one of {ASKERS}, not {who!r}")
        asker = self.asker
        before = (asker.who, asker.facts, asker.sink)
        asker.who, asker.facts, asker.sink = who, facts, sink
        try:
            yield
        finally:
            asker.who, asker.facts, asker.sink = before

    @contextlib.contextmanager
    def building(self) -> Iterator[None]:
        """The length of one ``Scheduler.__init__``: its executables read
        ``build``, its seconds go to ``setup["build_s"]`` and, between
        two calls of :meth:`enter_stage`, to the stage's."""
        since = time.perf_counter()
        try:
            with self.asking("build"):
                yield
        finally:
            self._end_stage()
            with self.lock:
                self.setup["builds"] += 1
                self.setup["build_s"] += time.perf_counter() - since

    def enter_stage(self, stage: str) -> None:
        """End the open stage of this thread's build and start ``stage``,
        which is also a ``setup/<stage>`` span on the thread's line of
        the profiler's host plane."""
        import jax

        self._end_stage()
        span = jax.profiler.TraceAnnotation(f"setup/{stage}")
        span.__enter__()
        self.asker.stage = (stage, time.perf_counter(), span)

    def _end_stage(self) -> None:
        if self.asker.stage is None:
            return
        stage, since, span = self.asker.stage
        self.asker.stage = None
        span.__exit__(None, None, None)
        with self.lock:
            self.setup[f"{stage}_s"] += time.perf_counter() - since

    # -- readers -----------------------------------------------------------

    def newest(self, limit: Optional[int] = None) -> list[dict]:
        """The newest ``limit`` entries (all that are kept, without one),
        oldest first."""
        if limit is not None and limit <= 0:
            return []
        with self.lock:
            return [dict(e) for e in list(self.entries)[-(limit or 0):]]

    def report(self) -> dict:
        """``{"setup": ..., "executables": totals by asked_by}``."""
        with self.lock:
            return {
                "setup": dict(self.setup),
                "executables": {w: dict(t) for w, t in self.totals.items()},
            }


# This process's record.  JAX's listeners are the process's, so it is too.
EXECUTABLES = ExecutableRecord()
_watching = False


def _watch_compiles() -> None:
    """Hand JAX's monitoring events to :data:`EXECUTABLES`, once a
    process however often it is asked."""
    global _watching
    if _watching:
        return
    _watching = True
    from jax import monitoring

    monitoring.register_event_listener(EXECUTABLES.on_event)
    monitoring.register_event_duration_secs_listener(EXECUTABLES.on_duration)


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
    """Device, compile cost, ``Scheduler.__init__``'s seconds by stage,
    executables by who asked, kernel paths taken and peak device memory
    of this process — what ``/health`` and ``chip_smoke.py`` print."""
    import jax

    from generativeaiexamples_tpu.ops.dispatch import TAKEN

    stats = jax.local_devices()[0].memory_stats() or {}
    record = EXECUTABLES.report()
    totals = record["executables"].values()
    return {
        "device": device_report(),
        # The record's sums over every asker: seconds in the backend
        # (XLA's compiles and the cache's reads), executables found in
        # the persistent cache and looked up in vain.
        "compile": {
            "compile_s": round(sum(t["backend_s"] for t in totals), 1),
            "cache_hits": sum(t["hit"] for t in totals),
            "cache_misses": sum(t["miss"] for t in totals),
            "cache_dir": jax.config.jax_compilation_cache_dir,
        },
        **record,
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
