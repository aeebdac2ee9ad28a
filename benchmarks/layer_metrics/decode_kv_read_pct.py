"""Share of the KV positions a dense walk of the decode window would read
(every slot x ``kv_bucket``) that the decode kernel's walk of each
decoding row's own blocks reads (``Stats.decode_kv_tokens_read`` /
``decode_kv_tokens_dense``, counted at each decode dispatch)."""

from counter_lib import ratio


def read(ctx):
    return ratio(
        ctx, ["decode_kv_tokens_read"], ["decode_kv_tokens_dense"], 100.0
    )
