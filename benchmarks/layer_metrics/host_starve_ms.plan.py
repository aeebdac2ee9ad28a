"""The part of ``host_starve_ms`` the tick thread spent in its ``plan``
phase (``Stats.device_starved_plan_s``); the four parts add up to it."""

from counter_lib import per_busy_tick_ms


def read(ctx):
    return per_busy_tick_ms(ctx, ["device_starved_plan_s"])
