"""Wall time of one busy tick: the tick thread's time in its five working
phases (``Stats.tick_phase_<phase>_s``, idle left out) over the ticks
that touched the device."""

from counter_lib import WORKING_PHASES, per_busy_tick_ms


def read(ctx):
    return per_busy_tick_ms(ctx, [f"tick_phase_{p}_s" for p in WORKING_PHASES])
