"""The part of ``dispatch_ms_per_site`` from the phase's start to the
site's first jitted call (``Stats.dispatch_h2d_s``, the span
``tick/dispatch/h2d``): numpy staging, the host-to-device arrays and the
sampling key's split."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["dispatch_h2d_s"], ["dispatch_sites"], 1000.0)
