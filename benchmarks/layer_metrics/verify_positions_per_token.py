"""Positions the stack computed in decode steps for each token they
emitted: d ``verify_positions`` / d ``decode_tokens_emitted`` (both
counted on the device by the verify chunk).  2.0 where no draft is kept
(two positions a row a step, one token), 1.0 if every draft were."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["verify_positions"], ["decode_tokens_emitted"])
