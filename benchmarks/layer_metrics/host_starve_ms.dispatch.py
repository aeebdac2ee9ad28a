"""The part of ``host_starve_ms`` the tick thread spent in its ``dispatch``
phase (``Stats.device_starved_dispatch_s``); the four parts add up to it."""

from counter_lib import per_busy_tick_ms


def read(ctx):
    return per_busy_tick_ms(ctx, ["device_starved_dispatch_s"])
