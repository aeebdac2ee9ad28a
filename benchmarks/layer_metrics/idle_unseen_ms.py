"""The device's idle time per busy tick that the program's own clock does
not account for: the traced window less the union of the device's
operations (``trace.window_s - trace.busy_s``), less the host time the
tick thread booked as starved between the window's two markers
(``Stats.device_starved_s``, which ``host_starve_ms`` reads), over the
ticks that touched the device.  0 is a clock that sees every gap, a
positive reading idle time it misses, a negative one time it counts
twice.  For the cells whose tick thread never runs out of work: where it
enters ``idle`` the device idles by design and no phase is to blame.
Nothing to read without a trace or without the counters."""


def read(ctx):
    trace, c = ctx["trace"], ctx.get("trace_counters")
    if trace is None or c is None or not trace["window_s"]:
        return None
    try:
        starved_s, ticks = c["device_starved_s"], c["busy_ticks"]
    except KeyError:
        return None
    if not ticks:
        return None
    return 1000.0 * (trace["window_s"] - trace["busy_s"] - starved_s) / ticks
