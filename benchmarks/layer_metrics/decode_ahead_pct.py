"""Of the decode chunks fetched, the share that was dispatched while the
chunk before it was still unfetched: with every slot taken the scheduler
sends chunk N+1 before it blocks on chunk N's tokens, so that the device
decodes through the host's gap between two ticks.  0 wherever a slot is
free (the next arrival's prefill leads the device's queue instead), in a
speculative scheduler and over the paged layout."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["decode_chunks_ahead"], ["decode_chunks"], 100.0)
