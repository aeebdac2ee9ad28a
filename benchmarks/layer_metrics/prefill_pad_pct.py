"""Share of the token positions handed to the prefill programs that were
padding: bucketed shape minus real tokens, padded batch rows included."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx,
        ["prefill_tokens_padded"],
        ["prefill_tokens_padded", "prefill_tokens_dispatched"],
        100.0,
    )
