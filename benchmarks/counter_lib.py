"""Ratios of the scheduler's own counters, for the per-layer readers
that read nothing else.

Every such reader takes ``ctx["trace_counters"]``: the deltas of
``Stats.snapshot()`` between the traced window's two markers, so that it
speaks of the same 10 s as the device metrics.  It returns ``None``
without them (``--trace 0``), on a zero denominator, and on a program
that lacks the counter (the commit before the one that added it): the
metric is then left out of the line.
"""

from __future__ import annotations

# The phases of the tick thread in which it has work (``idle`` is the
# sixth: blocked on an empty queue).
WORKING_PHASES = ("plan", "dispatch", "wait_device", "emit", "telemetry")


def ratio(ctx: dict, numerators, denominators, scale: float = 1.0):
    """``scale`` x sum of the ``numerators``' deltas / sum of the
    ``denominators``' deltas, or None where that cannot be read."""
    c = ctx.get("trace_counters")
    if c is None:
        return None
    try:
        num = sum(c[k] for k in numerators)
        den = sum(c[k] for k in denominators)
    except KeyError:
        return None
    if not den:
        return None
    return scale * num / den


def per_busy_tick_ms(ctx: dict, seconds_keys):
    """Milliseconds of ``seconds_keys`` per tick that touched the device."""
    return ratio(ctx, seconds_keys, ["busy_ticks"], 1000.0)
