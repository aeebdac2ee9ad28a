"""Of the prompt tokens the prefix index matched, the share that was
prefilled again because a recurrent state exists only at a saved
boundary: (``prefix_tokens_matched`` - ``prefix_tokens_reused``) / matched.
0 for a model whose state can be cut at any token."""


def read(ctx):
    c = ctx.get("trace_counters")
    if c is None or "prefix_tokens_matched" not in c or "prefix_tokens_reused" not in c:
        return None
    if not c["prefix_tokens_matched"]:
        return None
    return 100.0 * (c["prefix_tokens_matched"] - c["prefix_tokens_reused"]) / c["prefix_tokens_matched"]
