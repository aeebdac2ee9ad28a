"""Lanes that emitted a token per decode step: decoded tokens over
(decode chunks x steps per chunk).  The first token of a request comes
from its prefill and is not counted."""


def read(ctx):
    c = ctx["counters"]
    steps = c["decode_chunks"] * ctx["engine"]["decode_chunk_size"]
    if not steps:
        return None
    return (c["tokens_total"] - c["ttft_count"]) / steps
