"""What the program's own record says of set-up, for the per-layer
readers of the layer "engine set-up".

``runtime_report()`` (``generativeaiexamples_tpu/utils/jax_runtime.py``,
imported as ``run.py`` imports it: a reader runs in the process that
served) carries ``setup``, the seconds of ``Scheduler.__init__`` by
stage, and ``executables``, the totals of the record of every executable
JAX made or read from its cache, by who asked: ``build`` (inside
``Scheduler.__init__``), ``tick`` (a scheduler's tick thread: the
warm-up's first call of every step program) or ``other`` (the reference
check's in-process prefill, which runs after the window and is no part
of set-up).  Set-up ends before ``ctx["counters"]`` is first read and
nothing compiles inside the window (``no_compile_in_window``), so the
totals of ``build`` and ``tick`` after the window are set-up's.

Every reader returns ``None`` on a program that has no such record (the
commit before the one that added it): the metric is then left out of the
line.
"""

from __future__ import annotations

SETUP_ASKERS = ("build", "tick")


def report():
    """``runtime_report()`` if it has the record, else None."""
    try:
        from generativeaiexamples_tpu.utils.jax_runtime import runtime_report
    except ImportError:
        return None
    found = runtime_report()
    if "setup" not in found or "executables" not in found:
        return None
    return found


def build_s(found):
    """``setup.build_s`` of a report, or None."""
    if found is None:
        return None
    return found["setup"].get("build_s")


def total(found, *keys):
    """The sum of ``keys`` over the executables ``build`` and ``tick``
    asked for, or None where that cannot be read."""
    if found is None:
        return None
    try:
        return sum(
            found["executables"][who][k] for who in SETUP_ASKERS for k in keys
        )
    except KeyError:
        return None


def hit_pct(found):
    """Of the executables looked up in the persistent cache for ``build``
    and ``tick``, the share found; None where none was looked up."""
    hits, looked_up = total(found, "hit"), total(found, "hit", "miss")
    if not looked_up:
        return None
    return 100.0 * hits / looked_up
