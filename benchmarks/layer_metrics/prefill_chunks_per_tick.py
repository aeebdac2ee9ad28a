"""Prefill chunks dispatched per busy tick: each one lengthens the tick
that every decoding lane waits for."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["prefill_chunks"], ["busy_ticks"])
