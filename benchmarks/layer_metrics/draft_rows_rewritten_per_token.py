"""Rows a rejected draft left to be written again, for each token the
decode steps emitted: d ``draft_rows_rewritten`` / d
``decode_tokens_emitted`` (``serving_models.HybridServing``'s verify chunk
counts both on the device: for every decoding row whose draft the stack
did not keep, one row for each layer of the stack that keeps a row a
position, a latent row with its index key or a full layer's K and V).
The stack's layers where every draft is rejected (one token a step, a row
a layer written twice), 0 where every draft is kept.  Lower is better;
nothing to read from a program without the counters."""

from counter_lib import ratio


def read(ctx):
    return ratio(ctx, ["draft_rows_rewritten"], ["decode_tokens_emitted"])
