"""Host time per busy tick with nothing queued on the device: from the
fetch of a tick's last program to the return of the next jitted call
(``Stats.device_starved_s``; a lower bound of the device's idle time)."""

from counter_lib import per_busy_tick_ms


def read(ctx):
    return per_busy_tick_ms(ctx, ["device_starved_s"])
