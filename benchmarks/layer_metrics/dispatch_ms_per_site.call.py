"""The part of ``dispatch_ms_per_site`` inside the site's jitted calls
(``Stats.dispatch_call_s``, the span ``tick/dispatch/call``): the step
program and what is enqueued behind it (a boundary's snapshot, a graft,
a draft model's prefill)."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["dispatch_call_s"], ["dispatch_sites"], 1000.0)
