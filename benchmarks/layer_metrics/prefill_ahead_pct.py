"""Of the prefill chunks dispatched, the share sent a tick ahead: behind
the decode chunk and before the host blocked on its tokens, so that the
device ran them through the host's gap between two ticks.  A prompt's
first chunk never is (its admission sends it), nor is a chunk of a tick
with no decode chunk in it."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["prefill_chunks_ahead"], ["prefill_chunks"], 100.0)
